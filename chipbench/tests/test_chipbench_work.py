"""FLOPs and bytes of the fused round kernels (chipbench/work.py) against
counts worked by hand."""

from chipbench import work


def test_fused_gather_bytes_by_hand():
    flops, nbytes = work.fused_gather_cost(rows=3, D=10)
    # 3 rows x (3 tables x 10 + 5 scalars) float32, read once, written once
    assert flops == 0.0
    assert nbytes == 2 * 3 * 35 * 4


def test_fused_verify_commit_cost_by_hand():
    flops, nbytes = work.fused_verify_commit_cost(rows=2, D=10)
    assert flops == 14 * 2 * 10
    # reads y, g, xi, m_hat and 4 scalars; writes z and one accept flag
    assert nbytes == 2 * (40 + 4) * 4 + 2 * (10 + 1) * 4
