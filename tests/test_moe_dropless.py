"""The dropless expert layer of ``repro.nn.moe`` and what it leaves alone.

A model whose ``capacity_factor`` is None is dropless: every token reaches
its top_k experts through a sort by expert and grouped matmuls.  Here: the
layer equals the dense formula (every expert on every token, weighted by
its gate, 0 where unrouted) with the top_k gates renormalized or not as the
model says; a ``vmap`` over the layer (the engine maps the model over its
slots) equals the layer applied to each element; a dropless model refuses
an expert-parallel axis; weights are made in the model's ``param_dtype``;
and a model with capacity and no shared expert builds the parameter tree
it built before and never reaches the dropless path.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import BlockDesc, ModelConfig
from repro.configs.registry import get_denoiser_config
from repro.models.diffusion import denoiser_init
from repro.nn import moe as moe_lib
from repro.nn.param import unbox


def _cfg(**kw):
    base = dict(name="dropless-test", family="moe", n_layers=1, d_model=32,
                n_heads=2, n_kv_heads=2, d_ff=48, vocab_size=1,
                group=(BlockDesc("attn", moe=True),), n_experts=8, top_k=2,
                capacity_factor=None, norm_topk_prob=False,
                pos_embed="none", embed_inputs=False,
                compute_dtype="float32", remat=False)
    base.update(kw)
    return ModelConfig(**base)


def _dense(params, x, cfg):
    """Every expert on every token, weighted by its top_k gate (0 where the
    token was not routed to it), in float32 at full precision."""
    hi = jax.lax.Precision.HIGHEST
    probs = jax.nn.softmax(jnp.einsum("bld,de->ble", x, params["router"],
                                      precision=hi), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    gate = (top[..., None] * jax.nn.one_hot(idx, cfg.n_experts)).sum(-2)
    g = jnp.einsum("bld,edf->blef", x, params["w_gate"], precision=hi)
    u = jnp.einsum("bld,edf->blef", x, params["w_up"], precision=hi)
    out = jnp.einsum("blef,efd->bled", jax.nn.silu(g) * u, params["w_down"],
                     precision=hi)
    return (gate[..., None] * out).sum(-2)


def _params(cfg, seed=0):
    return unbox(moe_lib.moe_init(jax.random.PRNGKey(seed), cfg))


@pytest.mark.parametrize("renorm", [False, True])
def test_dropless_layer_is_the_dense_formula(renorm):
    cfg = _cfg(norm_topk_prob=renorm)
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 32))
    got, aux = moe_lib.moe_apply(p, x, cfg)
    want = _dense(p, x, cfg)
    err = float(jnp.max(jnp.abs(got - want)))
    # float32 sums in another order: rounding of a few ulps of the output
    assert err < 1e-5 * float(jnp.max(jnp.abs(want))), err
    assert np.isfinite(float(aux["moe_aux_loss"]))


def test_dropless_layer_under_vmap_is_the_layer_per_element():
    cfg = _cfg()
    p = _params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 3, 16, 32))
    mapped = jax.jit(jax.vmap(lambda xi: moe_lib.moe_apply(p, xi, cfg)[0]))(x)
    each = jnp.stack([moe_lib.moe_apply(p, xi, cfg)[0] for xi in x])
    np.testing.assert_allclose(np.asarray(mapped), np.asarray(each),
                               rtol=0, atol=1e-6)


def test_dropless_model_refuses_an_expert_parallel_axis():
    cfg = _cfg()
    x = jnp.zeros((1, 4, 32))
    with pytest.raises(ValueError, match="no expert-parallel dispatch"):
        moe_lib.moe_apply(_params(cfg), x, cfg, ep_axis="model")


def test_weights_are_made_in_the_models_param_dtype():
    dc = get_denoiser_config("qwen3-moe-a3b-smoke")
    dc = dataclasses.replace(dc, backbone=dataclasses.replace(
        dc.backbone, param_dtype="bfloat16", shared_ff=16))
    tree = jax.eval_shape(lambda k: unbox(denoiser_init(k, dc)),
                          jax.random.PRNGKey(0))
    assert {x.dtype for x in jax.tree_util.tree_leaves(tree)} == {
        jnp.dtype(jnp.bfloat16)}
    shared = tree["decoder"]["g0"]["shared_ffn"]
    assert shared["w_gate"].shape == (2, 64, 16)
    assert shared["w_down"].shape == (2, 16, 64)


# the qwen3-moe-a3b-smoke tree's expert leaves before the dropless layer
# and the shared expert existed: shape and sum of each, from PRNGKey(0)
CAPACITY_TREE = {
    "decoder/g0/ffn_norm/scale": ((2, 64), 0.0),
    "decoder/g0/moe/router": ((2, 64, 8), 0.36514371066732565),
    "decoder/g0/moe/w_down": ((2, 8, 128, 64), 13.122709437429023),
    "decoder/g0/moe/w_gate": ((2, 8, 64, 128), 4.562033105179012),
    "decoder/g0/moe/w_up": ((2, 8, 64, 128), -1.9044751568035707),
}


def test_capacity_model_keeps_its_tree_and_its_path(monkeypatch):
    dc = get_denoiser_config("qwen3-moe-a3b-smoke")
    b = dc.backbone
    assert b.capacity_factor == 8.0 and b.shared_ff == 0 and b.norm_topk_prob
    p = unbox(denoiser_init(jax.random.PRNGKey(0), dc))
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): x
            for path, x in jax.tree_util.tree_flatten_with_path(p)[0]}
    assert len(flat) == 15
    ffn = {n: x for n, x in flat.items() if "moe" in n or "ffn" in n}
    assert sorted(ffn) == sorted(CAPACITY_TREE)
    for n, (shape, total) in CAPACITY_TREE.items():
        assert ffn[n].shape == shape
        assert float(np.asarray(ffn[n], np.float64).sum()) == pytest.approx(
            total, rel=1e-6, abs=1e-9), n

    def never(*a, **k):
        raise AssertionError("a capacity model reached the dropless layer")

    monkeypatch.setattr(moe_lib, "_moe_apply_dropless", never)
    e = jax.tree_util.tree_map(lambda a: a[0], p["decoder"]["g0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 64))
    out, _ = moe_lib.moe_apply(e, x, b)
    # capacity 8 x 2 / 8 of 8 tokens clamps to every token: nothing drops,
    # and the renormalized gates give the dense formula
    want = _dense(e, x, b)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
