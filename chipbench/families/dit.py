"""The ``dit`` family: the registry's dense transformer denoiser.

Pre-RMSNorm blocks of non-causal self-attention and a SwiGLU MLP over the
noisy tokens, a sinusoidal position and time embedding, and an
x0-predicting output projection.  The family gives the harness four
things: the weights recipe (``leaf_shapes``), the plain float32 forward
pass (``denoise``), the FLOPs of one point (``flops``) and what the
reference assumes of the program's model beyond the widths the
configuration states (``program_departs``).  Nothing here imports the
program.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.reference import mm as _mm


def leaf_shapes(m: dict) -> dict:
    """{path: (shape, fan_in)} of the denoiser's weights; layers stacked."""
    n, d, ff = m["n_layers"], m["d_model"], m["d_ff"]
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    C, td = m["d_data"], m["time_dim"]
    return {
        "in_proj": ((C, d), C),
        "t_mlp1": ((td, d), td),
        "t_mlp2": ((d, d), d),
        "decoder/g0/attn_norm/scale": ((n, d), 0),
        "decoder/g0/attn/wq": ((n, d, h, hd), d),
        "decoder/g0/attn/wk": ((n, d, kv, hd), d),
        "decoder/g0/attn/wv": ((n, d, kv, hd), d),
        "decoder/g0/attn/wo": ((n, h, hd, d), h * hd),
        "decoder/g0/ffn_norm/scale": ((n, d), 0),
        "decoder/g0/ffn/w_gate": ((n, d, ff), d),
        "decoder/g0/ffn/w_up": ((n, d, ff), d),
        "decoder/g0/ffn/w_down": ((n, ff, d), ff),
        "final_norm/scale": ((d,), 0),
        "out_proj": ((d, C), d),
    }


def flops(m: dict) -> float:
    """FLOPs of one denoiser point (one noisy sample at one step).

    Every weight matrix costs 2 FLOPs per weight per token it multiplies;
    self-attention adds 4 L^2 d per layer (QK^T and PV, all heads).  The
    time embedding runs once per point.  Elementwise work (norms, SiLU,
    softmax, residual adds) is left out.
    """
    return point_flops(m, 3 * m["d_model"] * m["d_ff"])  # gate, up, down


def point_flops(m: dict, ffn_weights: float) -> float:
    """``flops`` with ``ffn_weights`` weights multiplying each token in the
    feed-forward half of a layer."""
    L, C = m["seq_len"], m["d_data"]
    d, layers = m["d_model"], m["n_layers"]
    q = m["n_heads"] * m["head_dim"]  # width of the query/output heads
    kv = m["n_kv_heads"] * m["head_dim"]
    per_layer = 2 * d * q + 2 * d * kv + ffn_weights  # q, o, k, v; the FFN
    per_token = layers * per_layer + C * d + d * C  # blocks, in_proj, out_proj
    attention = layers * 4 * L * L * q
    time_embed = 2 * (m["time_dim"] * d + d * d)
    return 2.0 * L * per_token + attention + time_embed


def program_departs(dc) -> list:
    """What the reference assumes of the program's ``DenoiserConfig`` that
    the configuration's model section does not state: the names of the
    assumptions that do not hold."""
    b = dc.backbone
    holds = {
        "no position embedding in the blocks": b.pos_embed == "none",
        "no conditioning input": not dc.d_cond,
        "no log time": not dc.time_log,
        "SwiGLU feed-forward": b.ffn_kind == "swiglu",
        "no attention softcap": not b.attn_softcap,
        "no q/k/v bias": not b.qkv_bias,
        "dense full-attention blocks": all(
            g.kind == "attn" and not g.moe and not g.window
            and g.window_per_repeat is None for g in b.group),
    }
    return [name for name, ok in holds.items() if not ok]


# ------------------------------------------------------------ the forward


def sinusoid(pos, dim, max_period=1e4):
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half) / half)
    args = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def rmsnorm(x, offset, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + offset)


def embed(w, t, y, m, mm):
    """The noisy tokens y (P, L, C) at times t (P,), embedded with their
    positions and time: (P, L, d)."""
    temb = jnp.tanh(mm("pk,kd->pd", sinusoid(t * 100.0, m["time_dim"]),
                       w["t_mlp1"]))
    temb = mm("pd,de->pe", temb, w["t_mlp2"])
    x = mm("plc,cd->pld", y, w["in_proj"])
    return x + sinusoid(jnp.arange(m["seq_len"]), m["d_model"])[None] \
        + temb[:, None, :]


def attention(x, g, m, mm):
    """x plus the block's pre-RMSNorm non-causal self-attention."""
    hd, eps = m["head_dim"], m["norm_eps"]
    rep = m["n_heads"] // m["n_kv_heads"]
    h = rmsnorm(x, g["attn_norm"]["scale"], eps)
    q = mm("pld,dhk->plhk", h, g["attn"]["wq"])
    k = mm("pld,dhk->plhk", h, g["attn"]["wk"])
    v = mm("pld,dhk->plhk", h, g["attn"]["wv"])
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = mm("plhk,pshk->phls", q, k) / math.sqrt(hd)
    o = mm("phls,pshk->plhk", jax.nn.softmax(s, axis=-1), v)
    return x + mm("plhk,hkd->pld", o, g["attn"]["wo"])


def swiglu(h, ffn, mm):
    """SwiGLU of the normed tokens h through one set of matrices."""
    a = jax.nn.silu(mm("pld,df->plf", h, ffn["w_gate"]))
    u = mm("pld,df->plf", h, ffn["w_up"])
    return mm("plf,fd->pld", a * u, ffn["w_down"])


def output(w, x, m, mm):
    x = rmsnorm(x, w["final_norm"]["scale"], m["norm_eps"])
    return mm("pld,dc->plc", x, w["out_proj"])


@partial(jax.jit, static_argnames=("m", "lower"))
def denoise(w, t, y, m, lower=None):
    """x0_hat for points y (P, L, C) at model times t (P,).  ``m`` is the
    configuration's model section as a tuple of items; ``lower`` rounds
    every matrix operand first (``reference.mm``)."""
    m = dict(m)
    mm = partial(_mm, lower=lower)

    def layer(x, g):
        x = attention(x, g, m, mm)
        h = rmsnorm(x, g["ffn_norm"]["scale"], m["norm_eps"])
        return x + swiglu(h, g["ffn"], mm), None

    x, _ = jax.lax.scan(layer, embed(w, t, y, m, mm),
                        w["decoder"]["g0"])  # one layer at a time
    return output(w, x, m, mm)
