"""Block registry: residual blocks for every family in the zoo.

All blocks share one interface so the group-scan decoder can drive them:

  init(key, cfg, desc)                        -> boxed params
  fwd(params, x, cfg, desc, ctx, window)      -> (x, aux)
  cache_init(params, cfg, desc, batch, L)     -> cache pytree
  prefill(params, x, cache, cfg, desc, ctx, w)-> (x, cache, aux)
  step(params, x1, cache, pos, cfg, desc, w)  -> (x1, cache)

``ctx``: dict(causal: bool, positions, vision, impl: "naive"|"chunked",
chunk: int).  ``window`` may be a traced per-layer scalar.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import BlockDesc, ModelConfig
from repro.nn import attention as attn
from repro.nn import ffn as ffn_lib
from repro.nn import moe as moe_lib
from repro.nn import ssm as ssm_lib
from repro.nn.layers import rmsnorm_init, rmsnorm_apply


def _maybe_ffn_init(key, cfg: ModelConfig, desc: BlockDesc):
    if cfg.d_ff == 0:
        return {}
    k1, k2 = jax.random.split(key)
    if desc.moe:
        p = {"ffn_norm": rmsnorm_init(k1, cfg.d_model),
             "moe": moe_lib.moe_init(k2, cfg)}
        if cfg.shared_ff:
            p["shared_ffn"] = ffn_lib.ffn_init(
                jax.random.fold_in(key, 2), cfg.d_model, cfg.shared_ff)
        return p
    return {
        "ffn_norm": rmsnorm_init(k1, cfg.d_model),
        "ffn": ffn_lib.ffn_init(k2, cfg.d_model, cfg.d_ff, cfg.ffn_kind),
    }


def _maybe_ffn_fwd(params, x, cfg: ModelConfig, desc: BlockDesc,
                   tp_axis: str | None = None, ep_axis: str | None = None,
                   seq_sharded: bool = False):
    # tp_axis: manual tensor parallelism for the dense FFN; ep_axis: expert
    # parallelism for the MoE expert stacks (local-expert gather +
    # all_to_all token exchange, see repro.nn.moe); seq_sharded: x is the
    # rank's Ulysses sequence slice — the dense FFN / norms are then
    # embarrassingly parallel (replicated weights, local rows) and the MoE
    # dispatch keeps the output local instead of psum-replicating it.
    # A MoE block's shared expert (``shared_ffn``) takes every token beside
    # its routed experts, as a dense FFN does.
    aux = {}
    if "moe" in params:
        hn = rmsnorm_apply(params["ffn_norm"], x)
        h, aux = moe_lib.moe_apply(params["moe"], hn, cfg, ep_axis=ep_axis,
                                   seq_sharded=seq_sharded)
        if "shared_ffn" in params:
            with jax.named_scope("moe/shared"):
                h = h + ffn_lib.ffn_apply(params["shared_ffn"], hn,
                                          d_ff=cfg.shared_ff, tp_axis=tp_axis)
        x = x + h
    elif "ffn" in params:
        x = x + ffn_lib.ffn_apply(params["ffn"], rmsnorm_apply(params["ffn_norm"], x),
                                  d_ff=cfg.d_ff, tp_axis=tp_axis)
    return x, aux


def _mp_ffn_kwargs(ctx):
    # model-parallel kwargs threaded from the decoder ctx into the FFN
    return dict(
        tp_axis=ctx.get("tp_axis"),
        ep_axis=ctx.get("ep_axis"),
        seq_sharded=ctx.get("sp_axis") is not None,
    )


# ------------------------------------------------------------------- attn


def attn_block_init(key, cfg, desc):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "attn_norm": rmsnorm_init(k1, cfg.d_model),
        "attn": attn.attn_init(k2, cfg),
    }
    p.update(_maybe_ffn_init(k3, cfg, desc))
    return p


def attn_block_fwd(params, x, cfg, desc, ctx, window):
    h = rmsnorm_apply(params["attn_norm"], x)
    h = attn.attn_fwd(
        params["attn"],
        h,
        cfg,
        window=window,
        causal=ctx.get("causal", True),
        positions=ctx.get("positions"),
        impl=ctx.get("impl", "naive"),
        chunk=ctx.get("chunk", 1024),
        tp_axis=ctx.get("tp_axis"),
        sp_axis=ctx.get("sp_axis"),
    )
    x = x + h
    return _maybe_ffn_fwd(params, x, cfg, desc, **_mp_ffn_kwargs(ctx))


def attn_block_cache_init(params, cfg, desc, batch, max_len, dtype=jnp.bfloat16):
    return attn.init_kv_cache(cfg, batch, max_len, dtype)


def attn_block_prefill(params, x, cache, cfg, desc, ctx, window):
    h = rmsnorm_apply(params["attn_norm"], x)
    h, cache = attn.attn_prefill(
        params["attn"], h, cache, cfg, window=window,
        positions=ctx.get("positions"), impl=ctx.get("impl", "chunked"),
        chunk=ctx.get("chunk", 1024),
    )
    x = x + h
    x, aux = _maybe_ffn_fwd(params, x, cfg, desc)
    return x, cache, aux


def attn_block_step(params, x1, cache, pos, cfg, desc, window):
    h = rmsnorm_apply(params["attn_norm"], x1)
    h, cache = attn.attn_step(params["attn"], h, cache, pos, cfg, window=window)
    x1 = x1 + h
    x1, _ = _maybe_ffn_fwd(params, x1, cfg, desc)
    return x1, cache


# ------------------------------------------------------------------ xattn


def xattn_block_init(key, cfg, desc):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "attn_norm": rmsnorm_init(k1, cfg.d_model),
        "attn": attn.attn_init(k2, cfg, cross=True),
    }
    p.update(_maybe_ffn_init(k3, cfg, desc))
    return p


def xattn_block_fwd(params, x, cfg, desc, ctx, window):
    vision = ctx["vision"]  # (B, Nv, d_model) stubbed frontend embeds
    h = rmsnorm_apply(params["attn_norm"], x)
    h = attn.attn_fwd(
        params["attn"], h, cfg, kv_x=vision,
        positions=ctx.get("positions"), causal=False,
        impl=ctx.get("impl", "naive"), chunk=ctx.get("chunk", 1024),
        tp_axis=ctx.get("tp_axis"),
    )
    x = x + h
    return _maybe_ffn_fwd(params, x, cfg, desc, **_mp_ffn_kwargs(ctx))


def xattn_block_cache_init(params, cfg, desc, batch, max_len, dtype=jnp.bfloat16):
    # cross-attn KV depends only on the (fixed) vision tokens
    nv = max(cfg.n_vision_tokens, 1)
    return attn.init_kv_cache(cfg, batch, nv, dtype)


def xattn_block_prefill(params, x, cache, cfg, desc, ctx, window):
    vision = ctx["vision"]
    h = rmsnorm_apply(params["attn_norm"], x)
    q, k_raw, v_raw = attn._project_qkv(
        params["attn"], h, vision, cfg,
        ctx.get("positions"), jnp.arange(vision.shape[1]), repeat_kv=False,
    )
    cache = {"k": k_raw.astype(cache["k"].dtype), "v": v_raw.astype(cache["v"].dtype)}
    reps = cfg.n_heads // cfg.n_kv_heads
    k = jnp.repeat(k_raw, reps, axis=2) if reps > 1 else k_raw
    v = jnp.repeat(v_raw, reps, axis=2) if reps > 1 else v_raw
    core = attn.attn_core_chunked if ctx.get("impl") == "chunked" else attn.attn_core_naive
    if ctx.get("impl") == "chunked":
        o = core(q, k, v, None, cfg.attn_softcap, ctx.get("chunk", 1024))
    else:
        o = core(q, k, v, None, cfg.attn_softcap)
    out = jnp.einsum("blhk,hkd->bld", o, params["attn"]["wo"].astype(x.dtype))
    out = jnp.tanh(params["attn"]["gate"]).astype(x.dtype) * out
    x = x + out
    x, aux = _maybe_ffn_fwd(params, x, cfg, desc)
    return x, cache, aux


def xattn_block_step(params, x1, cache, pos, cfg, desc, window):
    h = rmsnorm_apply(params["attn_norm"], x1)
    cdt = x1.dtype
    q = jnp.einsum("bld,dhk->blhk", h, params["attn"]["wq"].astype(cdt))
    if "bq" in params["attn"]:
        q = q + params["attn"]["bq"].astype(cdt)
    reps = cfg.n_heads // cfg.n_kv_heads
    kf = cache["k"].astype(cdt)
    vf = cache["v"].astype(cdt)
    if reps > 1:
        kf = jnp.repeat(kf, reps, axis=2)
        vf = jnp.repeat(vf, reps, axis=2)
    o = attn.attn_core_naive(q, kf, vf, None, cfg.attn_softcap)
    out = jnp.einsum("blhk,hkd->bld", o, params["attn"]["wo"].astype(cdt))
    out = jnp.tanh(params["attn"]["gate"]).astype(cdt) * out
    x1 = x1 + out
    x1, _ = _maybe_ffn_fwd(params, x1, cfg, desc)
    return x1, cache


# ------------------------------------------------------------------ hymba


def hymba_block_init(key, cfg, desc):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "mix_norm": rmsnorm_init(k1, cfg.d_model),
        "attn": attn.attn_init(k2, cfg),
        "mamba": ssm_lib.mamba_init(k3, cfg),
    }
    p.update(_maybe_ffn_init(k4, cfg, desc))
    return p


def hymba_block_fwd(params, x, cfg, desc, ctx, window):
    h = rmsnorm_apply(params["mix_norm"], x)
    a = attn.attn_fwd(
        params["attn"], h, cfg, window=window, causal=ctx.get("causal", True),
        positions=ctx.get("positions"), impl=ctx.get("impl", "naive"),
        chunk=ctx.get("chunk", 1024), tp_axis=ctx.get("tp_axis"),
    )
    m = ssm_lib.mamba_fwd(params["mamba"], h, cfg)
    x = x + 0.5 * (a + m)  # hymba: parallel attn+mamba heads, mean-fused
    return _maybe_ffn_fwd(params, x, cfg, desc, **_mp_ffn_kwargs(ctx))


def hymba_block_cache_init(params, cfg, desc, batch, max_len, dtype=jnp.bfloat16):
    return {
        "kv": attn.init_kv_cache(cfg, batch, max_len, dtype),
        "ssm": ssm_lib.mamba_init_state(params["mamba"], cfg, batch),
    }


def hymba_block_prefill(params, x, cache, cfg, desc, ctx, window):
    h = rmsnorm_apply(params["mix_norm"], x)
    a, kv = attn.attn_prefill(
        params["attn"], h, cache["kv"], cfg, window=window,
        positions=ctx.get("positions"), impl=ctx.get("impl", "chunked"),
        chunk=ctx.get("chunk", 1024),
    )
    m, state = ssm_lib.mamba_fwd(params["mamba"], h, cfg, return_state=True)
    x = x + 0.5 * (a + m)
    x, aux = _maybe_ffn_fwd(params, x, cfg, desc)
    return x, {"kv": kv, "ssm": state}, aux


def hymba_block_step(params, x1, cache, pos, cfg, desc, window):
    h = rmsnorm_apply(params["mix_norm"], x1)
    a, kv = attn.attn_step(params["attn"], h, cache["kv"], pos, cfg, window=window)
    m, st = ssm_lib.mamba_step(params["mamba"], h, cache["ssm"], cfg)
    x1 = x1 + 0.5 * (a + m)
    x1, _ = _maybe_ffn_fwd(params, x1, cfg, desc)
    return x1, {"kv": kv, "ssm": st}


# ------------------------------------------------------------- mlstm/slstm


def mlstm_block_init(key, cfg, desc):
    k1, k2 = jax.random.split(key)
    return {"norm": rmsnorm_init(k1, cfg.d_model), "cell": ssm_lib.mlstm_init(k2, cfg)}


def mlstm_block_fwd(params, x, cfg, desc, ctx, window):
    return x + ssm_lib.mlstm_fwd(params["cell"], rmsnorm_apply(params["norm"], x), cfg), {}


def mlstm_block_cache_init(params, cfg, desc, batch, max_len, dtype=jnp.bfloat16):
    return ssm_lib.mlstm_init_state(params["cell"], cfg, batch)


def mlstm_block_prefill(params, x, cache, cfg, desc, ctx, window):
    h = rmsnorm_apply(params["norm"], x)
    y, cache = ssm_lib.mlstm_fwd(params["cell"], h, cfg, return_state=True)
    return x + y, cache, {}


def mlstm_block_step(params, x1, cache, pos, cfg, desc, window):
    y, cache = ssm_lib.mlstm_step(params["cell"], rmsnorm_apply(params["norm"], x1), cache, cfg)
    return x1 + y, cache


def slstm_block_init(key, cfg, desc):
    k1, k2 = jax.random.split(key)
    return {"norm": rmsnorm_init(k1, cfg.d_model), "cell": ssm_lib.slstm_init(k2, cfg)}


def slstm_block_fwd(params, x, cfg, desc, ctx, window):
    return x + ssm_lib.slstm_fwd(params["cell"], rmsnorm_apply(params["norm"], x), cfg), {}


def slstm_block_cache_init(params, cfg, desc, batch, max_len, dtype=jnp.bfloat16):
    return ssm_lib.slstm_init_state(params["cell"], cfg, batch)


def slstm_block_prefill(params, x, cache, cfg, desc, ctx, window):
    h = rmsnorm_apply(params["norm"], x)
    y, cache = ssm_lib.slstm_fwd(params["cell"], h, cfg, return_state=True)
    return x + y, cache, {}


def slstm_block_step(params, x1, cache, pos, cfg, desc, window):
    y, cache = ssm_lib.slstm_step(params["cell"], rmsnorm_apply(params["norm"], x1), cache, cfg)
    return x1 + y, cache


BLOCKS = {
    "attn": (attn_block_init, attn_block_fwd, attn_block_cache_init, attn_block_prefill, attn_block_step),
    "xattn": (xattn_block_init, xattn_block_fwd, xattn_block_cache_init, xattn_block_prefill, xattn_block_step),
    "hymba": (hymba_block_init, hymba_block_fwd, hymba_block_cache_init, hymba_block_prefill, hymba_block_step),
    "mlstm": (mlstm_block_init, mlstm_block_fwd, mlstm_block_cache_init, mlstm_block_prefill, mlstm_block_step),
    "slstm": (slstm_block_init, slstm_block_fwd, slstm_block_cache_init, slstm_block_prefill, slstm_block_step),
}
