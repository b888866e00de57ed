"""A model family for tests: the registry's ``qwen3-moe-a3b-smoke``.

The ``dit`` family's blocks with a token-choice mixture of SwiGLU experts
in place of the MLP: each token's router probabilities are a softmax over
all ``n_experts``, its ``top_k`` largest are renormalized to sum to one,
and it takes that mix of its chosen experts' outputs.  The reference is
dropless; the program keeps ``capacity_factor`` x top_k / n_experts of a
row's tokens for each expert, all of them where that is at least 1, which
``program_departs`` requires.  A test copies this file into the family
directory, where a new architecture's family goes.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench.families import dit
from chipbench.reference import HIGHEST, mm as _mm


def leaf_shapes(m: dict) -> dict:
    n, d, ff, E = m["n_layers"], m["d_model"], m["d_ff"], m["n_experts"]
    shapes = {p: s for p, s in dit.leaf_shapes(m).items() if "/ffn/" not in p}
    shapes.update({
        "decoder/g0/moe/router": ((n, d, E), d),
        "decoder/g0/moe/w_gate": ((n, E, d, ff), d),
        "decoder/g0/moe/w_up": ((n, E, d, ff), d),
        "decoder/g0/moe/w_down": ((n, E, ff, d), ff),
    })
    return shapes


def flops(m: dict) -> float:
    """The router over every expert, the experts only where routed."""
    d = m["d_model"]
    return dit.point_flops(
        m, d * m["n_experts"] + m["top_k"] * 3 * d * m["d_ff"])


def program_departs(dc) -> list:
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
        "no token dropped": b.capacity_factor * b.top_k >= b.n_experts,
    }
    return [name for name, ok in holds.items() if not ok]


def experts(h, e, m, mm):
    """Every token of h (P, L, d) through its top_k experts."""
    probs = jax.nn.softmax(mm("pld,de->ple", h, e["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.maximum(top.sum(-1, keepdims=True), 1e-9)
    gate = jnp.einsum("plk,plke->ple", top,
                      jax.nn.one_hot(idx, m["n_experts"]))
    a = jax.nn.silu(mm("pld,edf->plef", h, e["w_gate"]))
    u = mm("pld,edf->plef", h, e["w_up"])
    out = mm("plef,efd->pled", a * u, e["w_down"])
    return jnp.einsum("ple,pled->pld", gate, out, precision=HIGHEST)


@partial(jax.jit, static_argnames=("m", "lower"))
def denoise(w, t, y, m, lower=None):
    m = dict(m)
    mm = partial(_mm, lower=lower)

    def layer(x, g):
        x = dit.attention(x, g, m, mm)
        h = dit.rmsnorm(x, g["ffn_norm"]["scale"], m["norm_eps"])
        return x + experts(h, g["moe"], m, mm), None

    x, _ = jax.lax.scan(layer, dit.embed(w, t, y, m, mm), w["decoder"]["g0"])
    return dit.output(w, x, m, mm)
