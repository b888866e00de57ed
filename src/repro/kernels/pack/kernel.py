"""Pallas TPU kernels: ragged row gather/scatter for packed verification.

The pack op is pure data movement — each output row is one dynamically
indexed row copy — so the kernel's job is to move exactly the packed rows
and keep the copies off the HLO gather/scatter path (which XLA lowers to one
dynamic-slice per row plus a concatenate on TPU).

Layout: every table stays in HBM.  The row index map is scalar-prefetched
into SMEM, and each BlockSpec's index map reads it, so the Pallas pipeline
DMAs one packed row per grid step (double-buffered) and no table is ever
held whole in VMEM — at a 1024x16 latent event a row is 64 KiB, so whole
tables ran out of VMEM from 512 rows (the fused gather's four tables) or
1024 rows (one table).  A row of D features
(D % 128 == 0) is viewed as a lane-dense (D // 128, 128) tile.

  gather grid step p:  out[p] = src[idx[p]]
  scatter grid step p: out[idx[p]] = vals[p], written into a zero table that
    is aliased as the output (rows no step visits stay zero); rows with
    idx[p] >= num_rows land on one extra drop row that is sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._padding import LANE


def as_row_tiles(a: jax.Array) -> jax.Array:
    """(N, D) -> (N, D // 128, 128): one lane-dense tile per row."""
    return a.reshape(a.shape[0], a.shape[1] // LANE, LANE)


def row_spec(tiles: jax.Array, row) -> pl.BlockSpec:
    """One row tile of a (N, T, 128) table per grid step; ``row(p, idx)``
    picks the row from the step and the prefetched index map."""
    return pl.BlockSpec((None,) + tiles.shape[1:],
                        lambda p, idx: (row(p, idx), 0, 0))


def _copy_kernel(idx_ref, *refs):
    n = len(refs) // 2
    for src, dst in zip(refs[:n], refs[n:]):
        dst[...] = src[...]


def gather_tables(tables, idx, interpret: bool = False,
                  name: str = "gather_rows"):
    """out_k[p] = tables[k][idx[p]] for every (N, D_k) table in ONE kernel
    named ``name``.

    D_k % 128 == 0; idx: (M,) int32 in [0, N).  Returns [(M, D_k), ...]."""
    tiles = [as_row_tiles(t) for t in tables]
    (M,) = idx.shape
    outs = pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(M,),
            in_specs=[row_spec(t, lambda p, idx: idx[p]) for t in tiles],
            out_specs=[row_spec(t, lambda p, idx: p) for t in tiles],
        ),
        out_shape=[jax.ShapeDtypeStruct((M,) + t.shape[1:], t.dtype)
                   for t in tiles],
        interpret=interpret,
        name=name,
    )(idx, *tiles)
    return [o.reshape(M, -1) for o in outs]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_pallas(src, idx, interpret: bool = False):
    """src: (N, D); idx: (M,) int32 in [0, N), D % 128 == 0.

    Returns out: (M, D) with out[p] = src[idx[p]].
    """
    (out,) = gather_tables([src], idx, interpret=interpret)
    return out


def _scatter_kernel(idx_ref, vals_ref, zeros_ref, out_ref):
    del idx_ref, zeros_ref  # zeros_ref IS out_ref's buffer (aliased)
    out_ref[...] = vals_ref[...]


@functools.partial(jax.jit, static_argnames=("num_rows", "interpret"))
def scatter_rows_pallas(vals, idx, num_rows: int, interpret: bool = False):
    """vals: (M, D); idx: (M,) int32; D % 128 == 0.

    Returns out: (num_rows, D) with out[idx[p]] = vals[p] for idx[p] in
    range; out-of-range rows dropped, unwritten rows zero.  In-range indices
    must be unique (the pack maps guarantee it).
    """
    tiles = as_row_tiles(vals)
    (M,) = idx.shape
    zeros = jnp.zeros((num_rows + 1,) + tiles.shape[1:], tiles.dtype)
    out = pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(M,),
            in_specs=[row_spec(tiles, lambda p, idx: p),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec(
                zeros, lambda p, idx: jnp.minimum(idx[p], num_rows)),
        ),
        out_shape=jax.ShapeDtypeStruct(zeros.shape, zeros.dtype),
        input_output_aliases={2: 0},  # operands: (idx, vals, zeros)
        interpret=interpret,
        name="scatter_rows",
    )(idx, tiles, zeros)
    return out[:num_rows].reshape(num_rows, -1)
