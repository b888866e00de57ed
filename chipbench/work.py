"""Operations and bytes, computed from shapes, for the work the benchmark
credits to the round kernels: one call of each fused round kernel.  The
FLOPs of one denoiser point are the model family's (``flops`` in
``families/<reference>.py``).

These are the algorithm's counts, not an implementation's: padding lanes,
masked rows and recomputation are not counted, so a share of a peak built
on them can only come out low, never high.
"""

from __future__ import annotations

F32 = 4  # bytes of a float32 / int32 element


def event_size(cfg: dict) -> int:
    return cfg["seq_len"] * cfg["d_data"]


def fused_gather_cost(rows: int, D: int, scalars: int = 5) -> tuple:
    """(flops, bytes) of one ``fused_gather`` call: ``rows`` packed rows of
    three float32 event tables (y_prev, xi, m_hat) of D elements each and a
    row of ``scalars`` float32 lanes, each read once and written once."""
    return 0.0, float(2 * rows * (3 * D + scalars) * F32)


def fused_verify_commit_cost(rows: int, D: int) -> tuple:
    """(flops, bytes) of one ``fused_verify_commit`` call over ``rows``
    packed rows of D elements.

    Reads y, g, xi, m_hat (4 D floats) and four scalars (u, sigma, A, B) per
    row; writes the committed row z (D floats) and its accept flag.  Per
    element: the target mean A y + B g (3), v = m_hat - m (1), the sums
    v.v and v.xi (4), the reflection xi - c v (2) and the select of the
    proposal m_hat + sigma xi or the reflected m + sigma xi_ref (4): 14.
    """
    flops = 14.0 * rows * D
    nbytes = rows * (4 * D + 4) * F32 + rows * (D + 1) * F32
    return flops, float(nbytes)
