"""FLOPs and bytes of chipbench/work.py against counts worked by hand."""

from chipbench import work

TINY = {"n_layers": 2, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
        "head_dim": 4, "d_ff": 16, "seq_len": 3, "d_data": 2,
        "time_dim": 4}


def test_denoiser_flops_tiny_by_hand():
    # per token and layer: q, k, v, o are 8x8 each (256 weights), gate, up
    # and down 8x16 each (384); in_proj 2x8 and out_proj 8x2 (32)
    per_token = 2 * (256 + 384) + 32
    attention = 2 * 4 * 3 * 3 * 8  # layers * 4 L^2 (heads * head_dim)
    time_embed = 2 * (4 * 8 + 8 * 8)
    assert work.denoiser_flops(TINY) == 2 * 3 * per_token + attention \
        + time_embed


def test_denoiser_flops_grouped_kv():
    gqa = dict(TINY, n_kv_heads=1)  # k and v shrink to 8x4 each
    saved = 2 * 3 * 2 * (2 * (8 * 8 - 8 * 4))  # 2 FLOPs, 3 tokens, 2 layers
    assert work.denoiser_flops(TINY) - work.denoiser_flops(gqa) == saved


def test_ldm_dit_point_matches_the_published_estimate():
    m = {"n_layers": 28, "d_model": 1152, "n_heads": 16, "n_kv_heads": 16,
         "head_dim": 72, "d_ff": 4608, "seq_len": 1024, "d_data": 16,
         "time_dim": 256}
    # 2 * 596M weights * 1024 tokens + 4 * 1024^2 * 1152 * 28 ~ 1.36e12
    assert abs(work.denoiser_flops(m) - 1.36e12) / 1.36e12 < 0.01


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
