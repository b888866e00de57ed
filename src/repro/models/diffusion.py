"""Diffusion denoiser head: any backbone as a DDPM mean oracle.

``DenoiserConfig`` wraps a backbone ``ModelConfig`` (run *non-causally*) with
a continuous data space (seq_len x d_data).  The model predicts
x0_hat = E[x0 | y_t] — exactly the ``g``/``m`` oracle ASD consumes (paper
Remark 2 / Eq. 4).  This is the DiT-style stand-in for the paper's UNet
denoisers and the diffusion-policy action denoiser (DESIGN.md §4, §9.2).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.decoder import decoder_init, decoder_fwd
from repro.nn.layers import rmsnorm_init, rmsnorm_apply, sinusoidal_embed
from repro.nn.param import param, zeros_init


@dataclasses.dataclass(frozen=True)
class DenoiserConfig:
    backbone: ModelConfig
    seq_len: int  # number of data tokens (action steps / latent patches)
    d_data: int  # channels per token
    d_cond: int = 0  # conditioning vector dim (diffusion-policy observations)
    time_log: bool = False  # log-transform t before embedding (SL time)
    time_dim: int = 256


def denoiser_init(key, dc: DenoiserConfig):
    cfg = dc.backbone
    ks = jax.random.split(key, 7)
    p = {
        "in_proj": param(ks[0], (dc.d_data, cfg.d_model), (None, "embed")),
        "t_mlp1": param(ks[1], (dc.time_dim, cfg.d_model), (None, "embed")),
        "t_mlp2": param(ks[2], (cfg.d_model, cfg.d_model), ("embed", "embed2")),
        "decoder": decoder_init(ks[3], cfg),
        "final_norm": rmsnorm_init(ks[4], cfg.d_model),
        "out_proj": param(ks[5], (cfg.d_model, dc.d_data), ("embed", None), zeros_init()),
    }
    if dc.d_cond:
        p["cond_proj"] = param(ks[6], (dc.d_cond, cfg.d_model), (None, "embed"))
    dtype = jnp.dtype(cfg.param_dtype)
    if dtype != jnp.float32:  # the weights are held in the model's dtype
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), p)
    return p


def denoiser_fwd(params, t, y, dc: DenoiserConfig, cond=None, impl: str = "naive",
                 chunk: int = 1024, tp_axis: str | None = None,
                 sp_axis: str | None = None, sp_size: int = 1,
                 ep_axis: str | None = None):
    """t: (B,) noise level / step; y: (B, L, d_data) -> x0_hat (B, L, d_data).
    cond: optional (B, d_cond) observation vector (diffusion policy).
    ``tp_axis``: mesh axis name for manual tensor parallelism — only valid
    inside a ``shard_map`` program whose param in_specs follow
    ``repro.distributed.sharding.tp_param_pspecs`` (the blocks then slice
    heads/hidden locally and all-reduce in-program).

    ``ep_axis``: expert parallelism for MoE backbones (param in_specs from
    ``mp_param_pspecs(expert=True)``); composes with ``tp_axis``.

    ``sp_axis``/``sp_size``: Ulysses sequence parallelism.  SP shards only
    activations (every weight stays replicated), so unlike TP/EP there is
    no param shape to detect — the caller states the factor explicitly
    (see ``sp_compatible``).  The residual stream runs sequence-sharded
    through the whole block stack: the embedded input is sliced to this
    rank's L/sp rows here, attention trades sequence for heads around its
    core (``repro.nn.attention``), and the denoised output is re-replicated
    by one psum of the zero-padded slices after ``out_proj``.  Mutually
    exclusive with ``tp_axis`` (both consume the head axis)."""
    cfg = dc.backbone
    cdt = jnp.dtype(cfg.compute_dtype)
    tf = t.astype(jnp.float32)
    if dc.time_log:
        tf = jnp.log1p(jnp.maximum(tf, 0.0))
    temb = sinusoidal_embed(tf * 100.0, dc.time_dim)
    temb = jnp.tanh(temb @ params["t_mlp1"].astype(jnp.float32))
    temb = temb @ params["t_mlp2"].astype(jnp.float32)  # (B, d_model)

    x = y.astype(cdt) @ params["in_proj"].astype(cdt)
    x = x + sinusoidal_embed(jnp.arange(dc.seq_len), cfg.d_model).astype(cdt)
    x = x + temb[:, None, :].astype(cdt)
    if cond is not None:
        cemb = cond.astype(cdt) @ params["cond_proj"].astype(cdt)
        x = x + cemb[..., None, :]
    positions = jnp.arange(dc.seq_len)
    if sp_axis is not None and sp_size > 1:
        assert tp_axis is None, "sp_axis and tp_axis are mutually exclusive"
        Lc = dc.seq_len // sp_size
        r = jax.lax.axis_index(sp_axis)
        x = jax.lax.dynamic_slice_in_dim(x, r * Lc, Lc, axis=1)
        positions = jax.lax.dynamic_slice(positions, (r * Lc,), (Lc,))
    ctx = dict(causal=False, positions=positions, vision=None,
               impl=impl, chunk=chunk, tp_axis=tp_axis,
               sp_axis=sp_axis if sp_size > 1 else None, ep_axis=ep_axis)
    x, _ = decoder_fwd(params["decoder"], x, cfg, ctx)
    x = rmsnorm_apply(params["final_norm"], x)
    out = (x @ params["out_proj"].astype(cdt)).astype(jnp.float32)
    if sp_axis is not None and sp_size > 1:
        full = jnp.zeros(out.shape[:1] + (dc.seq_len,) + out.shape[2:],
                         out.dtype)
        full = jax.lax.dynamic_update_slice_in_dim(
            full, out, r * (dc.seq_len // sp_size), axis=1)
        out = jax.lax.psum(full, sp_axis)  # re-replicate the denoised x0
    return out


def _bcast_cond(cond, m):
    return None if cond is None else jnp.broadcast_to(cond, (m,) + cond.shape[-1:])


def make_sl_model_fn(params, dc: DenoiserConfig, cond=None,
                     tp_axis: str | None = None, sp_axis: str | None = None,
                     sp_size: int = 1, ep_axis: str | None = None):
    """ASD/sequential-sampler oracle for the *SL* parametrization.

    The network is trained on standardized inputs x_in = y / sqrt(t^2 + t)
    (unit-ish variance for unit-variance data); returns E[x0 | y_t].
    ``cond``: optional (d_cond,) per-chain conditioning (vmap adds batch).
    ``tp_axis``/``sp_axis``/``ep_axis``: model parallelism
    (see ``denoiser_fwd``).
    """

    def model_fn(t, y):
        t32 = jnp.maximum(t.astype(jnp.float32), 1e-6)
        scale = jnp.sqrt(t32**2 + t32)
        y_in = y / scale.reshape(t.shape + (1,) * (y.ndim - t.ndim))
        return denoiser_fwd(params, t32, y_in, dc,
                            cond=_bcast_cond(cond, y.shape[0]), tp_axis=tp_axis,
                            sp_axis=sp_axis, sp_size=sp_size, ep_axis=ep_axis)

    return model_fn


def make_ddpm_model_fn(params, dc: DenoiserConfig, cond=None,
                       tp_axis: str | None = None, sp_axis: str | None = None,
                       sp_size: int = 1, ep_axis: str | None = None):
    """x0-predicting oracle in the DDPM parametrization (t = step index)."""

    def model_fn(t, y):
        return denoiser_fwd(
            params, t.astype(jnp.float32), y, dc,
            cond=_bcast_cond(cond, y.shape[0]), tp_axis=tp_axis,
            sp_axis=sp_axis, sp_size=sp_size, ep_axis=ep_axis
        )

    return model_fn


def sp_compatible(dc: DenoiserConfig, sp_size: int) -> tuple[bool, str]:
    """Can this denoiser run Ulysses sequence parallelism at ``sp_size``?

    SP slices the sequence through the whole block stack, so every block
    must tolerate seeing only its rows: recurrences (ssm/mamba/xlstm) scan
    the full sequence and cross-attention mixes a second stream — both are
    out.  The two all_to_all exchanges need the head and sequence axes to
    divide the shard count exactly."""
    cfg = dc.backbone
    if sp_size <= 1:
        return True, "sp_size <= 1 (no sequence sharding)"
    bad = [d.kind for d in cfg.group if d.kind != "attn"]
    if bad:
        return False, f"non-attn blocks in group: {sorted(set(bad))}"
    if cfg.n_heads % sp_size:
        return False, f"n_heads={cfg.n_heads} not divisible by sp={sp_size}"
    if dc.seq_len % sp_size:
        return False, f"seq_len={dc.seq_len} not divisible by sp={sp_size}"
    return True, "ok"


def tp_collective_payloads(params, specs, dc: DenoiserConfig) -> list[int]:
    """Per-point all-reduce payload schedule (bytes) of ONE denoiser call
    under the manual-TP layout ``specs`` (``tp_param_pspecs`` output).

    Each model-sharded row-parallel leaf (attention ``wo``, FFN ``w_down``)
    contributes one (L, d_model) activation psum per layer-stack row; stacked
    leaves (leading ``layers`` scan axis) count once per row.  This is the
    payload schedule the engine feeds ``measure_collective_seconds`` to
    calibrate ``EngineStats.collective_s``."""
    from jax.sharding import PartitionSpec as _P

    cfg = dc.backbone
    row_bytes = dc.seq_len * cfg.d_model * jnp.dtype(cfg.compute_dtype).itemsize
    payloads: list[int] = []
    is_p = lambda x: isinstance(x, _P)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = {tuple(k): s for k, s in
              jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_p)[0]}

    def mentions_model(spec):
        for e in spec:
            axes = (e,) if isinstance(e, str) else tuple(e or ())
            if "model" in axes:
                return True
        return False

    for path, leaf in flat_p:
        name = getattr(path[-1], "key", None)
        if name not in ("wo", "w_down"):
            continue
        spec = flat_s.get(tuple(path))
        if spec is None or not mentions_model(spec):
            continue
        base_ndim = 3 if name == "wo" else 2
        rows = int(leaf.shape[0]) if getattr(leaf, "ndim", base_ndim) > base_ndim else 1
        payloads.extend([int(row_bytes)] * rows)
    return payloads


def mp_collective_payloads(params, specs, dc: DenoiserConfig, *,
                           mp_size: int = 1, sp_size: int = 1) -> dict:
    """Per-point collective payload schedule (bytes), per collective KIND,
    of one denoiser call under the model-parallel layout ``specs``
    (``mp_param_pspecs`` output) at ``mp_size`` model shards / ``sp_size``
    sequence shards.

    Superset of ``tp_collective_payloads`` keyed by primitive so the engine
    can calibrate psum and all_to_all separately (their per-device wire
    bytes differ: ring all-reduce moves ~2(w-1)/w of the buffer, all_to_all
    (w-1)/w once):

      psum        TP row-parallel wo / w_down all-reduces; the EP
                  row-parallel combine (one per MoE layer row, skipped when
                  the stream is sequence-sharded); the single SP output
                  re-replication.
      all_to_all  EP token exchange (2 per MoE layer row) and the Ulysses
                  q/k/v + output exchanges (4 per attention layer row).
    """
    from jax.sharding import PartitionSpec as _P

    cfg = dc.backbone
    itemsize = jnp.dtype(cfg.compute_dtype).itemsize
    row_bytes = int(dc.seq_len * cfg.d_model * itemsize)
    psum: list[int] = []
    a2a: list[int] = []
    seq_sharded = sp_size > 1
    is_p = lambda x: isinstance(x, _P)
    flat_p = jax.tree_util.tree_flatten_with_path(params)[0]
    flat_s = {tuple(k): s for k, s in
              jax.tree_util.tree_flatten_with_path(specs, is_leaf=is_p)[0]}

    def mentions_model(spec):
        for e in spec or ():
            axes = (e,) if isinstance(e, str) else tuple(e or ())
            if "model" in axes:
                return True
        return False

    # EP token slice and its per-(row, expert) capacity (see nn.moe: the
    # exchange path routes L/mp local tokens; the non-dividing fallback is
    # exchange-free)
    ep_exchanges = mp_size > 1 and (seq_sharded or dc.seq_len % mp_size == 0)
    Lt = dc.seq_len // mp_size if ep_exchanges else dc.seq_len
    E, k = cfg.n_experts, cfg.top_k
    cap = 0
    if E and cfg.capacity_factor is not None:
        cap = min(int(max(1, -(-k * Lt * cfg.capacity_factor // E))), Lt)

    for path, leaf in flat_p:
        name = getattr(path[-1], "key", None)
        in_moe = any(getattr(p, "key", None) == "moe" for p in path)
        model_sharded = mentions_model(flat_s.get(tuple(path)))
        if name == "wo" and not in_moe:
            rows = int(leaf.shape[0]) if leaf.ndim > 3 else 1
            if model_sharded:  # TP row-parallel wo
                psum.extend([row_bytes] * rows)
            if seq_sharded:  # Ulysses: q/k/v out + o back per core
                xch = int((dc.seq_len // sp_size) * cfg.n_heads
                          * cfg.resolved_head_dim * itemsize)
                a2a.extend([xch] * (4 * rows))
        elif name == "w_down" and not in_moe and model_sharded:
            rows = int(leaf.shape[0]) if leaf.ndim > 2 else 1
            psum.extend([row_bytes] * rows)  # TP row-parallel FFN
        elif name == "w_gate" and in_moe and model_sharded:
            # one w_gate per MoE layer: (E, d, ff), stacked (layers, E, d, ff)
            rows = int(leaf.shape[0]) if leaf.ndim > 3 else 1
            if ep_exchanges:  # capacity rows out + expert outputs back
                xch = int(E * cap * cfg.d_model * itemsize)
                a2a.extend([xch] * (2 * rows))
            if not seq_sharded:  # EP row-parallel combine
                psum.extend([row_bytes] * rows)
    if seq_sharded:
        psum.append(int(dc.seq_len * dc.d_data * 4))  # f32 x0 re-replication
    return {"psum": psum, "all_to_all": a2a}


def ddpm_denoiser_loss(params, dc: DenoiserConfig, x0, key, abar, cond=None):
    """Standard DDPM x0-prediction loss.  x0: (B, L, d_data); abar: (K,)."""
    B = x0.shape[0]
    K = abar.shape[0]
    kt, kn = jax.random.split(key)
    s = jax.random.randint(kt, (B,), 0, K)
    ab = abar[s][:, None, None]
    eps = jax.random.normal(kn, x0.shape)
    y = jnp.sqrt(ab) * x0 + jnp.sqrt(1.0 - ab) * eps
    pred = denoiser_fwd(params, s.astype(jnp.float32), y, dc, cond=cond)
    return jnp.mean((pred - x0) ** 2)


def sl_denoiser_loss(params, dc: DenoiserConfig, x0, key, t_min=1e-2,
                     t_max=100.0, cond=None):
    """SL-parametrized x0-prediction loss with standardized inputs.

    y_t = t x0 + sqrt(t) xi; the net sees y_t / sqrt(t^2+t) and log1p(t).
    t is sampled log-uniformly over the grid range.
    """
    B = x0.shape[0]
    kt, kn = jax.random.split(key)
    logt = jax.random.uniform(
        kt, (B,), minval=jnp.log(t_min), maxval=jnp.log(t_max)
    )
    t = jnp.exp(logt)
    xi = jax.random.normal(kn, x0.shape)
    y = t[:, None, None] * x0 + jnp.sqrt(t)[:, None, None] * xi
    scale = jnp.sqrt(t**2 + t)[:, None, None]
    pred = denoiser_fwd(params, t, y / scale, dc, cond=cond)
    return jnp.mean((pred - x0) ** 2)
