"""The ``dit_moe`` family: DiT-MoE (Fei et al., arXiv:2407.11633) on the
registry's transformer block.

The ``dit`` family's blocks, embedding and output with the MLP replaced by
a sparse mixture of SwiGLU experts beside a shared one.  Each token's
router probabilities are a softmax over all ``n_experts`` logits; it takes
its ``top_k`` largest as they are (renormalized to sum to one only where
``norm_topk_prob`` says so) and sums its chosen experts' outputs weighted
by them.  A shared SwiGLU expert of width ``shared_ff`` takes every token
and is added.  No token is dropped.

The reference runs every token through every expert and weights each
output by that expert's gate, exactly 0 for an expert it was not routed
to: it shares nothing with the program's sort and grouped matmuls.  The
weights are held as the configuration makes them (bfloat16 for the
published widths) and each product upcasts its operands inside the scan,
one layer at a time.  Nothing here imports the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.families import dit
from chipbench.reference import mm as _mm

GMM_CALLS = 3  # grouped matmuls a layer: gate, up, down


def leaf_shapes(m: dict) -> dict:
    """{path: (shape, fan_in)}: the ``dit`` leaves with the MLP's replaced by
    the router, the stacked experts and the shared expert."""
    n, d, ff, E = m["n_layers"], m["d_model"], m["d_ff"], m["n_experts"]
    sff = m["shared_ff"]
    shapes = {p: s for p, s in dit.leaf_shapes(m).items() if "/ffn/" not in p}
    shapes.update({
        "decoder/g0/moe/router": ((n, d, E), d),
        "decoder/g0/moe/w_gate": ((n, E, d, ff), d),
        "decoder/g0/moe/w_up": ((n, E, d, ff), d),
        "decoder/g0/moe/w_down": ((n, E, ff, d), ff),
        "decoder/g0/shared_ffn/w_gate": ((n, d, sff), d),
        "decoder/g0/shared_ffn/w_up": ((n, d, sff), d),
        "decoder/g0/shared_ffn/w_down": ((n, sff, d), sff),
    })
    return shapes


def flops(m: dict) -> float:
    """FLOPs of one point: the router over every expert, the routed experts
    only where a token is routed (top_k of them), the shared expert on
    every token, and the ``dit`` family's attention, embedding and output."""
    d = m["d_model"]
    return dit.point_flops(m, d * m["n_experts"]
                           + m["top_k"] * 3 * d * m["d_ff"]
                           + 3 * d * m["shared_ff"])


def gmm_work(m: dict) -> dict:
    """The grouped matmul kernel's work: its FLOPs and the bytes of its
    routed rows (read and written) per point, and the expert weights one
    call reads, in the dtype they are held in.  A point sends
    seq_len x top_k rows through each of a layer's three calls: gate and up
    (d -> ff), down (ff -> d)."""
    d, ff, layers = m["d_model"], m["d_ff"], m["n_layers"]
    rows = m["seq_len"] * m["top_k"]
    item = jnp.dtype(m["compute_dtype"]).itemsize
    return {
        "flops_per_point": float(layers * GMM_CALLS * 2 * rows * d * ff),
        "row_bytes_per_point": float(layers * rows * GMM_CALLS * (d + ff)
                                     * item),
        "weight_bytes_per_call": float(
            m["n_experts"] * d * ff * jnp.dtype(m["param_dtype"]).itemsize),
    }


def program_departs(dc) -> list:
    """What the reference assumes of the program's ``DenoiserConfig`` that
    the configuration's model section does not state: the names of the
    assumptions that do not hold."""
    b = dc.backbone
    holds = {
        "no position embedding in the blocks": b.pos_embed == "none",
        "no conditioning input": not dc.d_cond,
        "no log time": not dc.time_log,
        "no attention softcap": not b.attn_softcap,
        "no q/k/v bias": not b.qkv_bias,
        "full-attention blocks with an expert FFN": all(
            g.kind == "attn" and g.moe and not g.window
            and g.window_per_repeat is None for g in b.group),
        "dropless: no expert capacity": b.capacity_factor is None,
        "a shared expert": b.shared_ff > 0,
    }
    return [name for name, ok in holds.items() if not ok]


# ------------------------------------------------------------ the forward


def gates(h, router, m, mm):
    """(P, L, E) weight of each expert for each token of h: its softmax
    probability where it is among the token's top_k, else exactly 0."""
    probs = jax.nn.softmax(mm("pld,de->ple", h, router), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    if m["norm_topk_prob"]:
        top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    onehot = jax.nn.one_hot(idx, m["n_experts"], dtype=jnp.float32)
    return (top[..., None] * onehot).sum(-2)


def experts(h, g, m, mm):
    """The layer's feed-forward half on the normed tokens h (P, L, d): every
    expert on every token, weighted by its gate, plus the shared expert."""
    e = g["moe"]
    w = gates(h, e["router"], m, mm)
    a = jax.nn.silu(mm("pld,edf->plef", h, e["w_gate"]))
    u = mm("pld,edf->plef", h, e["w_up"])
    out = mm("plef,efd->pled", a * u, e["w_down"])
    routed = (w[..., None] * out).sum(-2)
    return routed + dit.swiglu(h, g["shared_ffn"], mm)


@partial(jax.jit, static_argnames=("m", "lower"))
def denoise(w, t, y, m, lower=None):
    """x0_hat for points y (P, L, C) at model times t (P,); ``m`` is the
    configuration's model section as a tuple of items, ``lower`` rounds
    every matrix operand first (``reference.mm``)."""
    m = dict(m)
    mm = partial(_mm, lower=lower)

    def layer(x, g):
        x = dit.attention(x, g, m, mm)
        h = dit.rmsnorm(x, g["ffn_norm"]["scale"], m["norm_eps"])
        return x + experts(h, g, m, mm), None

    x, _ = jax.lax.scan(layer, dit.embed(w, t, y, m, mm), w["decoder"]["g0"])
    return dit.output(w, x, m, mm)
