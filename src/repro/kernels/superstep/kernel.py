"""Pallas TPU kernels: the fused packed-round body (repro.serving.packing).

A packed speculation round is plan -> pack -> verify -> commit.  The plan
(one proposal call + the theta-shaped rollout) and the verify model call are
the model's own programs; everything else the round launches — the ragged
gather of live points, the five scalar-window gathers, the GRS
accept/reflect pass, and the two commit scatters — used to be seven separate
XLA programs per scan iteration.  The two kernels here collapse them to two:

  ``fused_gather_pallas``    the pack side: ONE program gathers the y_prev /
      xi / m_hat event rows AND the packed scalar table (t, u, A, B, sigma
      stacked as lanes of one (N, C) table) for every packed position.  The
      tables stay in HBM; each grid step DMAs one packed row out of each by
      its scalar-prefetched index (``repro.kernels.pack.kernel``).

  ``_fused_commit_kernel``   the verify/commit side: ONE program computes
      the target mean m = A * y + B * g in-register, runs the full GRS math
      (``repro.kernels.grs.kernel.grs_select``, the same accept/reflect code
      as the GRS kernel), and writes the per-row sample z and accept flag
      straight into the (num_rows, ...) slot-window tables — the commit
      scatter rides the same pass instead of a separate program.  The tables
      are zero buffers aliased as the outputs, so unwritten rows stay zero;
      out-of-range rows (the pack's padding lanes) land on one extra drop
      row that is sliced off.

Layout contracts match kernels/pack: one packed row per grid step, feature
axes lane-padded (128) by ops.py and viewed as (D // 128, 128) tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.grs.kernel import grs_select
from repro.kernels.pack.kernel import as_row_tiles, gather_tables, row_spec

# lanes of the per-row scalar tile handed to the verify/commit kernel
SCALAR_LANES = ("u", "sigma", "A", "B")


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_gather_pallas(y, xi, mh, sc, idx, interpret: bool = False):
    """y, xi, mh: (N, D); sc: (N, C); idx: (M,) int32 in [0, N).

    D % 128 == 0, C % 128 == 0.  Returns the four packed row sets
    ((M, D) x 3, (M, C)) in one kernel launch.
    """
    return tuple(gather_tables([y, xi, mh, sc], idx, interpret=interpret,
                               name="fused_gather"))


def _total(x):
    """Sum of a (T, 128) tile as a (1, 1) value: lanes, then sublanes."""
    return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0, keepdims=True)


def _fused_commit_kernel(idx_ref, sc_ref, y_ref, g_ref, xi_ref, mh_ref,
                         z0_ref, a0_ref, z_ref, acc_ref):
    del idx_ref, z0_ref, a0_ref  # the zero tables ARE z_ref/acc_ref (aliased)
    sc = sc_ref[...].astype(jnp.float32)  # (1, 128)
    u, sig, A, B = (sc[:, k:k + 1] for k in range(len(SCALAR_LANES)))
    y = y_ref[...].astype(jnp.float32)  # (T, 128)
    g = g_ref[...].astype(jnp.float32)
    xi = xi_ref[...].astype(jnp.float32)
    mh = mh_ref[...].astype(jnp.float32)

    # the verifier's target mean, fused in-register — same affine form the
    # packed round materializes between its model call and the GRS pass
    mt = A * y + B * g
    v = mh - mt
    z, accept = grs_select(u, sig, xi, mh, mt, _total(v * v), _total(v * xi))
    z_ref[...] = z.astype(z_ref.dtype)
    acc_ref[...] = jnp.broadcast_to(accept, acc_ref.shape).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("num_rows", "interpret"))
def fused_verify_commit_pallas(u, sigma, A, B, y, g, xi, mh, idx,
                               num_rows: int, interpret: bool = False):
    """u, sigma, A, B: (M,); y, g, xi, mh: (M, D); idx: (M,) int32.

    D % 128 == 0.  Returns (z_table: (num_rows, D), accept_table:
    (num_rows,) int32): the GRS outputs scattered to their slot-window rows;
    idx[p] >= num_rows drops row p, unwritten rows zero.  In-range indices
    must be unique (the pack maps guarantee it).
    """
    (M,) = idx.shape
    lanes = jnp.stack([u, sigma, A, B], axis=-1).astype(jnp.float32)
    scal = jnp.zeros((M, 1, 128), jnp.float32).at[:, 0, :lanes.shape[1]].set(
        lanes)
    rows = [as_row_tiles(a) for a in (y, g, xi, mh)]
    z0 = jnp.zeros((num_rows + 1,) + rows[0].shape[1:], xi.dtype)
    a0 = jnp.zeros((num_rows + 1, 1, 128), jnp.int32)
    packed = lambda p, idx: p  # noqa: E731
    target = lambda p, idx: jnp.minimum(idx[p], num_rows)  # noqa: E731
    z, acc = pl.pallas_call(
        _fused_commit_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(M,),
            in_specs=[row_spec(scal, packed)]
            + [row_spec(r, packed) for r in rows]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=[row_spec(z0, target), row_spec(a0, target)],
        ),
        out_shape=[jax.ShapeDtypeStruct(z0.shape, z0.dtype),
                   jax.ShapeDtypeStruct(a0.shape, a0.dtype)],
        # operands: (idx, scal, y, g, xi, mh, z0, a0)
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
        name="fused_verify_commit",
    )(idx, scal, *rows, z0, a0)
    return z[:num_rows].reshape(num_rows, -1), acc[:num_rows, 0, 0]
