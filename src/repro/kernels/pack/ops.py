"""Jit'd public wrappers for the pack kernel: arbitrary event shapes, padding
to the TPU lane boundary (via the shared kernels/_padding helper — the same
semantics the GRS wrapper uses), interpret-mode fallback on CPU, and an
``impl="ref"`` escape hatch to the pure-jnp reference."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels._backend import resolve_interpret
from repro.kernels._padding import LANE, pad_dim
from repro.kernels.pack.kernel import gather_rows_pallas, scatter_rows_pallas
from repro.kernels.pack.ref import gather_rows_ref, scatter_rows_ref


def _collapse(a: jax.Array):
    """(R, *event) -> (R, D); returns (rows, event_shape, D)."""
    event_shape = a.shape[1:]
    D = math.prod(event_shape) if event_shape else 1
    return a.reshape(a.shape[0], D), event_shape, D


def gather_rows(
    src: jax.Array,
    idx: jax.Array,
    impl: str = "kernel",
    interpret: bool | None = None,
) -> jax.Array:
    """out[p] = src[idx[p]] for a (N, *event) row table and (M,) indices."""
    if impl == "ref":
        return gather_rows_ref(src, idx)
    interpret = resolve_interpret(interpret)
    src2, event_shape, D = _collapse(src)
    M = idx.shape[0]
    src2 = pad_dim(src2, (-D) % LANE, axis=1)
    out = gather_rows_pallas(src2, idx.astype(jnp.int32), interpret=interpret)
    return out[:, :D].reshape((M,) + event_shape)


def scatter_rows(
    vals: jax.Array,
    idx: jax.Array,
    num_rows: int,
    impl: str = "kernel",
    interpret: bool | None = None,
) -> jax.Array:
    """Inverse of gather: route (M, *event) rows to a zeroed (num_rows, *event)
    table; ``idx[p] >= num_rows`` drops row p (the pack's padding lanes)."""
    if impl == "ref":
        return scatter_rows_ref(vals, idx, num_rows)
    interpret = resolve_interpret(interpret)
    vals2, event_shape, D = _collapse(vals)
    vals2 = pad_dim(vals2, (-D) % LANE, axis=1)
    out = scatter_rows_pallas(vals2, idx.astype(jnp.int32), num_rows,
                              interpret=interpret)
    return out[:, :D].reshape((num_rows,) + event_shape)
