"""Pallas TPU kernel: diagonal linear recurrence h_t = a_t * h_{t-1} + b_t.

The sequential inner loop of every SSM in the zoo (mamba / hymba selective
scan; the mLSTM normalizer shares the same structure).  The feature axis
(din*N collapsed) is embarrassingly parallel -> tiled over the grid; the
time axis is tiled with the running state carried in VMEM scratch across
the innermost grid dimension, so HBM sees each (a, b) element exactly once
(the scan is bandwidth-bound: 3 streams in/out, zero FLOP reuse).

Within a time tile the recurrence is a lax.fori_loop over rows — VPU
elementwise work fully resident in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_T = 256
BLOCK_D = 512


def _scan_kernel(a_ref, b_ref, h_ref, carry_scr, *, block_t: int):
    lk = pl.program_id(2)

    @pl.when(lk == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    def body(i, h):
        h = a_ref[0, i].astype(jnp.float32) * h + b_ref[0, i].astype(jnp.float32)
        h_ref[0, i] = h.astype(h_ref.dtype)
        return h

    carry_scr[...] = jax.lax.fori_loop(0, block_t, body, carry_scr[...])


@functools.partial(jax.jit, static_argnames=("block_t", "block_d", "interpret"))
def ssm_scan(a, b, *, block_t: int = BLOCK_T, block_d: int = BLOCK_D,
             interpret: bool = False):
    """a, b: (B, L, D) -> h: (B, L, D), h_t = a_t h_{t-1} + b_t, h_0 = b_0.
    L % block_t == 0 and D % block_d == 0 (ops.py pads)."""
    B, L, D = a.shape
    block_t = min(block_t, L)
    block_d = min(block_d, D)
    assert L % block_t == 0 and D % block_d == 0
    grid = (B, D // block_d, L // block_t)
    spec = pl.BlockSpec((1, block_t, block_d), lambda bi, dj, lk: (bi, lk, dj))
    kernel = functools.partial(_scan_kernel, block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((B, L, D), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_d,), jnp.float32)],
        interpret=interpret,
        name="ssm_scan",
    )(a, b)
