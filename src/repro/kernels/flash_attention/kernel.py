"""Pallas TPU kernel: flash attention (online softmax over KV blocks).

Covers every attention variant in the zoo: causal, sliding-window (gemma2
local / hymba), logit softcap (gemma2), GQA (KV pre-repeated to full heads —
the head axis is the mesh-sharded axis, see DESIGN.md §5).

Grid: (B*H, n_q_blocks, n_kv_blocks), kv innermost; the (m, l, acc) online
softmax state lives in VMEM scratch and persists across the kv dimension.
Block shapes default to 128x128 — MXU-aligned — and the q/kv tiles stream
HBM->VMEM once per block pair, the flash IO pattern.  Fully-masked causal /
out-of-window block pairs are skipped with pl.when (block-sparse schedule).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal: bool, window: int, softcap: float, block_q: int,
                  block_k: int, n_kv: int, seq_q: int, seq_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # block-level schedule: skip fully-masked pairs
    relevant = True
    if causal:
        relevant = k_start <= q_start + block_q - 1
    if window:
        relevant = jnp.logical_and(
            relevant, q_start - (k_start + block_k - 1) < window
        )

    @pl.when(relevant)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, dh)
        k = k_ref[0].astype(jnp.float32)  # (block_k, dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) / (q.shape[-1] ** 0.5)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_idx < seq_k
        if causal:
            mask &= k_idx <= q_idx
        if window:
            mask &= (q_idx - k_idx) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_k",
                     "true_seq_k", "interpret"),
)
def flash_attention(
    q, k, v, *, causal: bool = True, window: int = 0, softcap: float = 0.0,
    block_q: int = DEFAULT_BLOCK, block_k: int = DEFAULT_BLOCK,
    true_seq_k: int | None = None, interpret: bool = False,
):
    """q: (BH, Lq, dh); k, v: (BH, Skv, dh) — heads collapsed into rows.
    Lq/Skv are padded to the block sizes by ops.py; ``true_seq_k`` masks the
    padded KV tail."""
    BH, Lq, dh = q.shape
    Skv = k.shape[1]
    block_q = min(block_q, Lq)
    block_k = min(block_k, Skv)
    assert Lq % block_q == 0 and Skv % block_k == 0
    nq, nk = Lq // block_q, Skv // block_k
    kernel = functools.partial(
        _flash_kernel,
        causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, n_kv=nk, seq_q=Lq,
        seq_k=true_seq_k if true_seq_k is not None else Skv,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Lq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
