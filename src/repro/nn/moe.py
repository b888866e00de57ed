"""Mixture-of-Experts FFN: token-choice top-k routing, TPU-native dispatch.

A model with a ``capacity_factor`` keeps per-expert capacity (GShard-with-
dropping semantics).  Its dispatch is the capacity-gather formulation: per
expert, gather its top-C assigned tokens (no (N, E, C) one-hot blow-up),
run a batched-over-experts SwiGLU, scatter-add back weighted by the router
probs (renormalized over the top_k unless ``norm_topk_prob`` is off).

A model without one (``capacity_factor`` None) is dropless: every token
reaches its top_k experts.  Its dispatch sorts the (token, expert) pairs by
expert and runs the experts as grouped matmuls over exactly the routed rows
(``repro.kernels.gmm``), so a call costs tokens x top_k expert rows however
the router skews; the products are unsorted and summed with their gates.
It runs on one device: it has no expert-parallel form.

Two execution layouts share the capacity routing math:

  replicated   every device holds the full (E, d, ff) expert stacks and
               computes every expert (the train path and mp=1 serving).
  expert-parallel (``ep_axis``)  the expert stacks are sharded over the mesh
               ``model`` axis (each device owns E/mp experts, see
               ``repro.distributed.sharding.EP_VERIFY_SIGS``); tokens are
               partitioned over the same axis, each rank routes + gathers
               its own token slice for ALL experts, a ``jax.lax.all_to_all``
               hands every rank its local experts' capacity rows (and a
               second one hands the outputs back), and a psum-based
               row-parallel combine restores the replicated output — all
               inside one shard_map program, so dispatch count per boundary
               is unchanged.

Covers dbrx (E=16 top-4) and qwen3-moe (E=128 top-8 fine-grained d_ff=768).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.gmm import gmm
from repro.nn.param import param, normal_init


def moe_init(key, cfg: ModelConfig):
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": param(k1, (d, E), ("embed", None), normal_init(0.02)),
        "w_gate": param(k2, (E, d, ff), ("experts", "embed", "mlp")),
        "w_up": param(k3, (E, d, ff), ("experts", "embed", "mlp")),
        "w_down": param(k4, (E, ff, d), ("experts", "mlp", "embed")),
    }


def _route(params, x, cfg: ModelConfig, capacity):
    """Token-choice routing + per-(row, expert) capacity selection.

    x: (B, L, d) -> gate_vals/token_idx/keep (B, E, C) plus the (E,)
    routed-token and router-prob fractions the aux loss is built from.
    Capacity C defaults to ceil(top_k * L * cf / E) per batch *row* and is
    clamped to L (an expert can never hold more than every token of a row),
    which preserves the renormalized gate weights: clamping changes how many
    tokens fit, never the per-token routing weight.
    """
    B, L, _ = x.shape
    E, k = cfg.n_experts, cfg.top_k

    logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (B,L,E)
    top_p, top_idx = jax.lax.top_k(probs, k)  # (B,L,k)
    if cfg.norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # sparse (B,L,E) weight matrix of the selected experts
    sel = jax.nn.one_hot(top_idx, E, dtype=jnp.float32)  # (B,L,k,E)
    weights = jnp.einsum("blk,blke->ble", top_p, sel)  # (B,L,E)

    if capacity is None:
        capacity = int(max(1, -(-k * L * cfg.capacity_factor // E)))
    capacity = min(capacity, L)

    # per (batch row, expert): pick its top-C tokens by routing weight
    w_t = weights.transpose(0, 2, 1)  # (B,E,L)
    gate_vals, token_idx = jax.lax.top_k(w_t, capacity)  # (B,E,C)
    keep = gate_vals > 0.0

    frac_tokens = jnp.mean(sel.sum(2), axis=(0, 1))  # (E,) fraction routed
    frac_probs = jnp.mean(probs, axis=(0, 1))
    return gate_vals, token_idx, keep, frac_tokens, frac_probs


def _expert_ffn(params, xg, cdt):
    """Batched-over-experts SwiGLU on the capacity-gathered tokens.
    xg: (B, E_local, C, d) against (E_local, ...) expert stacks."""
    wg = params["w_gate"].astype(cdt)
    wu = params["w_up"].astype(cdt)
    wd = params["w_down"].astype(cdt)
    g = jnp.einsum("becd,edf->becf", xg, wg)
    u = jnp.einsum("becd,edf->becf", xg, wu)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(cdt) * u
    return jnp.einsum("becf,efd->becd", h, wd)  # (B,E_local,C,d)


def _aux_loss(frac_tokens, frac_probs, cfg: ModelConfig):
    # Switch-style load-balancing auxiliary loss
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs) / cfg.top_k


def moe_apply(params, x, cfg: ModelConfig, capacity: int | None = None,
              ep_axis: str | None = None, seq_sharded: bool = False):
    """x: (B, L, d) -> (B, L, d), aux dict with load-balancing loss.

    ``ep_axis``: mesh axis name for expert parallelism under ``shard_map``
    — taken only when the expert stacks are actually the LOCAL shard
    (``w_gate.shape[0] != cfg.n_experts``), so replicated params compile the
    exact unsharded program (the same shape-detection contract as the
    TP-aware attention/FFN forwards).  ``seq_sharded`` marks x as already
    the rank's (B, L/mp, d) sequence slice (the Ulysses-composed path): the
    dispatch then skips its own token slice and the output stays local.
    """
    if cfg.capacity_factor is None:
        if ep_axis is not None:
            raise ValueError(
                f"{cfg.name}: a dropless MoE (capacity_factor None) has no "
                "expert-parallel dispatch; serve it on one device per model")
        return _moe_apply_dropless(params, x, cfg)
    E_local = params["w_gate"].shape[0]
    if ep_axis is not None and E_local != cfg.n_experts:
        return _moe_apply_ep(params, x, cfg, capacity, ep_axis, seq_sharded)

    B, L, d = x.shape
    cdt = x.dtype
    gate_vals, token_idx, keep, ft, fp = _route(params, x, cfg, capacity)

    xg = jnp.take_along_axis(
        x[:, None], token_idx[..., None], axis=2
    )  # (B,E,C,d)
    xg = xg * keep[..., None].astype(cdt)
    y_e = _expert_ffn(params, xg, cdt)
    y_e = y_e * (gate_vals * keep)[..., None].astype(cdt)

    # scatter-add expert outputs back to token positions
    out = jnp.zeros((B, L, d), cdt)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None, None], token_idx.shape)
    out = out.at[bidx, token_idx].add(y_e)
    return out, {"moe_aux_loss": _aux_loss(ft, fp, cfg)}


def _moe_apply_dropless(params, x, cfg: ModelConfig):
    """Every token through its top_k experts (see ``_dropless``)."""
    out, frac_tokens, frac_probs = _dropless(cfg)(params, x)
    return out, {"moe_aux_loss": _aux_loss(frac_tokens, frac_probs, cfg)}


@functools.lru_cache(maxsize=None)
def _dropless(cfg: ModelConfig):
    """The dropless layer for ``cfg``: (params, x (B, L, d)) -> (out, the
    (E,) routed-token and router-prob fractions).

    Tokens are independent, so a ``vmap`` over x (the engine maps the
    model over its slots) is the same layer over all the batch's tokens at
    once: its rule folds the mapped axis into B, so that one sort and one
    set of grouped matmuls serve every slot.  The fractions are then
    pooled over the batch."""
    E, k = cfg.n_experts, cfg.top_k

    @jax.custom_batching.custom_vmap
    def layer(params, x):
        B, L, d = x.shape
        cdt = x.dtype
        xf = x.reshape(B * L, d)
        with jax.named_scope("moe/route"):
            logits = (xf @ params["router"].astype(cdt)).astype(jnp.float32)
            probs = jax.nn.softmax(logits, axis=-1)  # (N, E)
            top_p, top_idx = jax.lax.top_k(probs, k)  # (N, k)
            if cfg.norm_topk_prob:
                top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True),
                                            1e-9)
        with jax.named_scope("moe/dispatch"):
            expert = top_idx.reshape(-1)  # pair p: token p // k, its p % k
            order = jnp.argsort(expert, stable=True)
            sizes = jnp.bincount(expert, length=E).astype(jnp.int32)
            rows = xf[order // k]  # (N * k, d), grouped by expert
        with jax.named_scope("moe/experts"):
            g = gmm(rows, params["w_gate"].astype(cdt), sizes)
            u = gmm(rows, params["w_up"].astype(cdt), sizes)
            h = jax.nn.silu(g.astype(jnp.float32)).astype(cdt) * u
            y = gmm(h, params["w_down"].astype(cdt), sizes)  # (N * k, d)
        with jax.named_scope("moe/combine"):
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
            y = y[back].reshape(B * L, k, d).astype(jnp.float32)
            out = (top_p[..., None] * y).sum(1).astype(cdt)
        frac_tokens = jnp.mean(jax.nn.one_hot(top_idx, E).sum(1), axis=0)
        return out.reshape(B, L, d), frac_tokens, probs.mean(0)

    @layer.def_vmap
    def _merged(axis_size, in_batched, params, x):
        p_batched, x_batched = in_batched
        if any(jax.tree_util.tree_leaves(p_batched)):
            raise NotImplementedError(
                "the dropless MoE layer maps over tokens, not weights")
        if not x_batched:
            return layer(params, x), (False, False, False)
        out, ft, fp = layer(params, x.reshape((-1,) + x.shape[2:]))
        return (out.reshape(x.shape), ft, fp), (True, False, False)

    return layer


def _moe_apply_ep(params, x, cfg: ModelConfig, capacity, ep_axis: str,
                  seq_sharded: bool):
    """Expert-parallel dispatch: local-expert gather + all_to_all token
    exchange + combine, inside the enclosing shard_map program.

    Token partition: each rank owns a contiguous L/mp slice of the sequence
    (its natural shard under Ulysses; carved out of the replicated input
    otherwise).  Each rank routes ITS tokens against the full (replicated)
    router and capacity-gathers them for ALL experts; the first all_to_all
    splits the expert axis so every rank receives, sender-major along the
    capacity axis, exactly its E/mp local experts' token rows; the local
    SwiGLU runs on 1/mp of the expert stacks; the second all_to_all inverts
    the exchange, restoring global expert order over local tokens; gating +
    scatter-add combine locally.  When the input was replicated, a psum of
    the zero-padded local slices (the row-parallel combine) restores the
    replicated full-sequence output — so the block boundary still ends on
    the same collective shape as the TP dense FFN.

    When L doesn't divide the axis (and the sequence isn't already sharded)
    the token exchange is skipped: every rank routes the FULL token set and
    computes only its expert block, with the same psum combine — exchange-
    free EP, correct for any L.
    """
    E, E_local = cfg.n_experts, params["w_gate"].shape[0]
    mp = E // E_local
    cdt = x.dtype
    r = jax.lax.axis_index(ep_axis)
    B, L, d = x.shape

    if not seq_sharded and L % mp:
        # exchange-free fallback: full-token routing, local expert block
        gate_vals, token_idx, keep, ft, fp = _route(params, x, cfg, capacity)
        sl = lambda a: jax.lax.dynamic_slice_in_dim(
            a, r * E_local, E_local, axis=1)
        gate_l, idx_l, keep_l = sl(gate_vals), sl(token_idx), sl(keep)
        xg = jnp.take_along_axis(x[:, None], idx_l[..., None], axis=2)
        xg = xg * keep_l[..., None].astype(cdt)
        y = _expert_ffn(params, xg, cdt)
        y = y * (gate_l * keep_l)[..., None].astype(cdt)
        out = jnp.zeros((B, L, d), cdt)
        bidx = jnp.broadcast_to(jnp.arange(B)[:, None, None], idx_l.shape)
        out = out.at[bidx, idx_l].add(y)
        out = jax.lax.psum(out, ep_axis)  # row-parallel combine
        return out, {"moe_aux_loss": _aux_loss(ft, fp, cfg)}

    if seq_sharded:
        xl, Lc = x, L  # caller already owns its (B, L/mp, d) slice
    else:
        Lc = L // mp
        xl = jax.lax.dynamic_slice_in_dim(x, r * Lc, Lc, axis=1)

    gate_vals, token_idx, keep, ft, fp = _route(params, xl, cfg, capacity)
    # per-slice routing stats -> global aux loss (slices are equal-sized,
    # so the global fractions are the mean of the per-rank fractions)
    ft = jax.lax.pmean(ft, ep_axis)
    fp = jax.lax.pmean(fp, ep_axis)

    xg = jnp.take_along_axis(
        xl[:, None], token_idx[..., None], axis=2
    )  # (B,E,C,d): this rank's tokens, capacity-gathered for ALL experts
    xg = xg * keep[..., None].astype(cdt)
    # token exchange: split the expert axis (rank s keeps experts
    # [s*E/mp, (s+1)*E/mp)), concatenate sender-major along capacity
    xg = jax.lax.all_to_all(
        xg, ep_axis, split_axis=1, concat_axis=2, tiled=True
    )  # (B, E_local, mp*C, d)
    y = _expert_ffn(params, xg, cdt)
    # return exchange: hand each sender back its C rows (inverts the above,
    # restoring (B, E, C, d) in GLOBAL expert order over local tokens)
    y = jax.lax.all_to_all(
        y, ep_axis, split_axis=2, concat_axis=1, tiled=True)
    y = y * (gate_vals * keep)[..., None].astype(cdt)

    out_l = jnp.zeros((B, Lc, d), cdt)
    bidx = jnp.broadcast_to(jnp.arange(B)[:, None, None], token_idx.shape)
    out_l = out_l.at[bidx, token_idx].add(y)
    aux = {"moe_aux_loss": _aux_loss(ft, fp, cfg)}
    if seq_sharded:
        return out_l, aux  # stream stays sequence-sharded between blocks
    out = jnp.zeros((B, L, d), cdt)
    out = jax.lax.dynamic_update_slice_in_dim(out, out_l, r * Lc, axis=1)
    return jax.lax.psum(out, ep_axis), aux  # row-parallel combine
