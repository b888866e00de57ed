"""Pallas TPU kernel: fused Gaussian Rejection Sampler (paper Alg 3).

One VMEM pass per row block fuses everything the verifier needs per
speculation slot: v = m_hat - m, the two reductions <v, xi> and ||v||^2, the
accept test, and BOTH branch outputs (accepted proposal sample and reflected
exact sample) selected per row.  On TPU this turns the verifier's ~6
elementwise HLO ops + 2 reductions into a single kernel launch per round —
the GRS cost is what the paper identifies as the non-model overhead of ASD.

Layout: rows = collapsed (theta * batch) speculation slots, cols = collapsed
event dims padded to the 128-lane boundary by ops.py.  Each grid step owns a
(ROW_BLK, D) tile; reductions run over the full feature dim in-register.

The accept predicate stays a float32 0/1 mask inside the kernel: Mosaic
cannot truncate a selected i8 vector back to i1, so a boolean ``where`` over
the (ROW_BLK, 1) accept column does not compile for the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROW_BLK = 8
_EPS = 1e-20


def grs_select(u, sig, xi, mh, mt, vnorm2, vdotxi):
    """The GRS accept test and both branch outputs, from the reductions.

    ``u``/``sig``/``vnorm2``/``vdotxi`` broadcast against the (.., D) rows
    ``xi``/``mh``/``mt``.  Returns ``(z, accept)`` with ``accept`` a float32
    1.0/0.0 mask shaped like the reductions.  Shared by this kernel and the
    fused verify/commit kernel (``repro.kernels.superstep``), so the two
    compute the same bits for the same reductions.
    """
    v = mh - mt
    safe_sig = jnp.where(sig > 0, sig, 1.0)
    log_ratio = -(vdotxi / safe_sig + vnorm2 / (2.0 * safe_sig * safe_sig))
    pass_test = jnp.where(
        jnp.log(jnp.maximum(u, _EPS)) <= jnp.minimum(log_ratio, 0.0), 1.0, 0.0)
    means_equal = jnp.where(vnorm2 <= 0.0, 1.0, 0.0)
    accept = jnp.where(sig > 0, pass_test, means_equal)

    safe_vn = jnp.where(vnorm2 > 0, vnorm2, 1.0)
    coef = 2.0 * vdotxi / safe_vn
    xi_refl = jnp.where(vnorm2 > 0, xi - coef * v, xi)
    z = jnp.where(accept > 0, mh + sig * xi, mt + sig * xi_refl)
    return z, accept


def _grs_kernel(u_ref, sig_ref, xi_ref, mh_ref, m_ref, z_ref, acc_ref):
    xi = xi_ref[...].astype(jnp.float32)  # (R, D)
    mh = mh_ref[...].astype(jnp.float32)
    mt = m_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)  # (R, 1)
    sig = sig_ref[...].astype(jnp.float32)  # (R, 1)

    v = mh - mt
    vnorm2 = jnp.sum(v * v, axis=1, keepdims=True)  # (R, 1)
    vdotxi = jnp.sum(v * xi, axis=1, keepdims=True)
    z, accept = grs_select(u, sig, xi, mh, mt, vnorm2, vdotxi)
    z_ref[...] = z.astype(z_ref.dtype)
    acc_ref[...] = accept.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grs_pallas(u, sigma, xi, m_hat, m, interpret: bool = False):
    """u, sigma: (R,); xi, m_hat, m: (R, D) with D % 128 == 0.

    Returns (z: (R, D), accept: (R,) int32).
    """
    R, D = xi.shape
    assert R % ROW_BLK == 0, (R, ROW_BLK)
    grid = (R // ROW_BLK,)
    row_spec = pl.BlockSpec((ROW_BLK, D), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec((ROW_BLK, 1), lambda i: (i, 0))
    z, acc = pl.pallas_call(
        _grs_kernel,
        grid=grid,
        in_specs=[scalar_spec, scalar_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec, scalar_spec],
        out_shape=[
            jax.ShapeDtypeStruct((R, D), xi.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.int32),
        ],
        interpret=interpret,
        name="grs",
    )(u[:, None], sigma[:, None], xi, m_hat, m)
    return z, acc[:, 0]
