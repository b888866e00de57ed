"""Jit'd public wrappers for the fused round kernels: arbitrary event
shapes, lane padding via the shared kernels/_padding helper, backend
resolution via kernels/_backend, and an ``impl="ref"`` escape hatch to the
pure-jnp references (the engine default — bit-identical to the unfused
packed round by construction)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels._backend import resolve_interpret
from repro.kernels._padding import LANE, pad_dim
from repro.kernels.superstep.kernel import (
    fused_gather_pallas,
    fused_verify_commit_pallas,
)
from repro.kernels.superstep.ref import (
    fused_gather_ref,
    fused_verify_commit_ref,
)


def _collapse(a: jax.Array):
    """(R, *event) -> (R, D); returns (rows, event_shape, D)."""
    event_shape = a.shape[1:]
    D = math.prod(event_shape) if event_shape else 1
    return a.reshape(a.shape[0], D), event_shape, D


def fused_gather(
    y_tbl: jax.Array,
    xi_tbl: jax.Array,
    mh_tbl: jax.Array,
    scal_tbl: jax.Array,
    idx: jax.Array,
    impl: str = "ref",
    interpret: bool | None = None,
):
    """The pack side of a fused round in one kernel: gather the y_prev / xi
    / m_hat event rows ((N, *event) each) AND the packed scalar lanes
    ((N, C): t, u, A, B, sigma stacked) at positions ``idx`` (M,).

    Returns ((M, *event) x 3, (M, C)).  Padding positions must carry
    idx == 0 (they re-read row 0 and are dropped at the commit scatter).
    """
    if impl == "ref":
        return fused_gather_ref(y_tbl, xi_tbl, mh_tbl, scal_tbl, idx)
    interpret = resolve_interpret(interpret)
    y2, event_shape, D = _collapse(y_tbl)
    xi2, _, _ = _collapse(xi_tbl)
    mh2, _, _ = _collapse(mh_tbl)
    C = scal_tbl.shape[1]
    M = idx.shape[0]
    pad_d = (-D) % LANE
    y2 = pad_dim(y2, pad_d, axis=1)
    xi2 = pad_dim(xi2, pad_d, axis=1)
    mh2 = pad_dim(mh2, pad_d, axis=1)
    sc2 = pad_dim(scal_tbl, (-C) % LANE, axis=1)
    oy, oxi, omh, osc = fused_gather_pallas(
        y2, xi2, mh2, sc2, idx.astype(jnp.int32), interpret=interpret)
    unpack = lambda o: o[:, :D].reshape((M,) + event_shape)  # noqa: E731
    return unpack(oy), unpack(oxi), unpack(omh), osc[:, :C]


def fused_verify_commit(
    y: jax.Array,
    g: jax.Array,
    xi: jax.Array,
    mh: jax.Array,
    A: jax.Array,
    B: jax.Array,
    u: jax.Array,
    sigma: jax.Array,
    idx: jax.Array,
    num_rows: int,
    impl: str = "ref",
    interpret: bool | None = None,
):
    """The verify/commit side of a fused round in one kernel: target mean
    ``m = A y + B g``, the GRS accept/reflect pass, and the commit scatter
    of z/accept into the (num_rows, ...) slot-window tables.

    y/g/xi/mh: (M, *event); A/B/u/sigma: (M,); idx: (M,) with
    idx[p] >= num_rows dropping row p.  Returns (z_table (num_rows, *event),
    accept_table (num_rows,) bool); unwritten rows zero.
    """
    if impl == "ref":
        return fused_verify_commit_ref(y, g, xi, mh, A, B, u, sigma, idx,
                                       num_rows)
    interpret = resolve_interpret(interpret)
    y2, event_shape, D = _collapse(y)
    g2, _, _ = _collapse(g)
    xi2, _, _ = _collapse(xi)
    mh2, _, _ = _collapse(mh)
    pad_d = (-D) % LANE
    y2, g2, xi2, mh2 = (pad_dim(a, pad_d, axis=1) for a in (y2, g2, xi2, mh2))
    z, acc = fused_verify_commit_pallas(
        u, sigma, A, B, y2, g2, xi2, mh2, idx.astype(jnp.int32), num_rows,
        interpret=interpret)
    z_tbl = z[:, :D].reshape((num_rows,) + event_shape)
    return z_tbl, acc.astype(bool)
