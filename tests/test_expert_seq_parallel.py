"""Expert- and sequence-parallel verify on the serving model axis.

The contract under test (ISSUE 10):

  * ``mp_param_pspecs(expert=True)`` shards ONLY the ``(E, d, ff)`` expert
    stacks over the ``model`` axis; ``tensor=True`` reproduces the PR 7
    ``tp_param_pspecs`` layout leaf-for-leaf, and non-dividing axes fall
    back to replication with a one-time ``repro.serving`` WARNING naming
    the leaf and the axis size.
  * EP ``moe_apply`` (local-expert gather + all_to_all token exchange +
    row-parallel combine) matches the dense dispatch within allclose and
    is run-twice deterministic; the non-dividing-L fallback and the
    seq-sharded (Ulysses-composed) variant hold the same parity.
  * Ulysses SP ``denoiser_fwd`` (sequence-sharded residual stream, seq<->
    head all_to_alls around every attention core) matches the replicated
    forward; sp=1 through the same call path is bit-identical.
  * Engine level: ep=1 is bit-identical to the PR 7 TP path per sample in
    both dispatch shapes; ep>1 / sp>1 are allclose + deterministic with
    per-device expert params at 1/mp; per-kind collective calibration
    (psum vs all_to_all) lands in ``EngineStats``.

Multi-device cases skip on a single-device install; CI runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import (
    paper_diffusion_policy_smoke,
    qwen3_moe_a3b_smoke,
)
from repro.core.schedules import ddpm as ddpm_schedule
from repro.distributed.sharding import (
    EP_VERIFY_SIGS,
    measure_collective_seconds,
    measure_collective_seconds_by_kind,
    mp_param_pspecs,
    serving_mesh,
    tp_param_pspecs,
)
from repro.models.diffusion import (
    denoiser_fwd,
    denoiser_init,
    make_ddpm_model_fn,
    mp_collective_payloads,
    sp_compatible,
    tp_collective_payloads,
)
from repro.nn import moe as moe_lib
from repro.nn.param import unbox
from repro.serving.engine import Request
from repro.serving.router import make_router
from repro.serving.sharded import ShardedASDEngine

needs2 = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >= 2 devices (set XLA_FLAGS="
           "--xla_force_host_platform_device_count)")
needs4 = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >= 4 devices (set XLA_FLAGS="
           "--xla_force_host_platform_device_count)")

THETA = 4
K = 12


class _FakeMesh:
    """mp_param_pspecs only reads mesh.shape — layout units must not need
    real devices."""

    def __init__(self, model=2):
        self.shape = {"model": model}
        self.axis_names = ("slots", "model")


@pytest.fixture(scope="module")
def moe_model():
    dc = qwen3_moe_a3b_smoke()  # 2 layers, 4 heads, E=8 top-2, cf=8
    params = unbox(denoiser_init(jax.random.PRNGKey(0), dc))
    boxed = jax.eval_shape(
        lambda k: denoiser_init(k, dc), jax.random.PRNGKey(0))
    sched = ddpm_schedule(K=K)
    return dc, params, boxed, sched


@pytest.fixture(scope="module")
def sp_model():
    dc = paper_diffusion_policy_smoke()  # dense attn-only, 4 heads, L=8
    params = unbox(denoiser_init(jax.random.PRNGKey(0), dc))
    boxed = jax.eval_shape(
        lambda k: denoiser_init(k, dc), jax.random.PRNGKey(0))
    sched = ddpm_schedule(K=K)
    return dc, params, boxed, sched


def _requests(dc, n, seed0=100):
    rng = np.random.default_rng(seed0)
    return [
        Request(i, key=jax.random.PRNGKey(seed0 + i),
                y0=rng.standard_normal(
                    (dc.seq_len, dc.d_data)).astype(np.float32))
        for i in range(n)
    ]


def _engine(dc, params, sched, *, mp=1, boxed=None, ep=False, sp=1,
            legacy_tp=False, **kw):
    base = dict(
        schedule=sched, event_shape=(dc.seq_len, dc.d_data),
        num_slots=4, theta=THETA, eager_head=True, noise_mode="counter",
        keep_trajectory=False, params=params,
        router=make_router("round-robin"),
    )
    base.update(kw)
    if mp > 1:
        mesh = serving_mesh(base.get("shards", 1), mp)
        if legacy_tp:  # the exact PR 7 construction, for bit-identity
            specs = tp_param_pspecs(boxed, mesh)
            payloads = tp_collective_payloads(params, specs, dc)
        else:
            specs = mp_param_pspecs(boxed, mesh, tensor=sp == 1, expert=ep)
            payloads = mp_collective_payloads(
                params, specs, dc, mp_size=mp, sp_size=sp)
        factory = lambda p, cond: make_ddpm_model_fn(
            p, dc,
            tp_axis="model" if sp == 1 else None,
            sp_axis="model" if sp > 1 else None, sp_size=sp,
            ep_axis="model" if ep else None)
        return ShardedASDEngine(
            factory, model_shards=mp, param_specs=specs,
            collective_payloads=payloads, **base)
    return ShardedASDEngine(
        lambda p, cond: make_ddpm_model_fn(p, dc), **base)


def _leaf_by_name(tree, name):
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if getattr(path[-1], "key", None) == name:
            return leaf
    raise KeyError(name)


def _axes_of(spec):
    return [a for e in tuple(spec)
            for a in ((e,) if isinstance(e, str) else tuple(e or ()))]


# -- layout units (device-count independent) --------------------------------


def test_ep_pspecs_shard_expert_stacks_only(moe_model):
    """expert=True moves exactly the EP_VERIFY_SIGS leaves onto the model
    axis (leading experts dim); the router and every non-MoE leaf keep the
    tensor-parallel layout decision."""
    dc, _, boxed, _ = moe_model
    specs = mp_param_pspecs(boxed, _FakeMesh(2), tensor=False, expert=True)
    for name in ("w_gate", "w_up", "w_down"):
        spec = _leaf_by_name(specs, name)
        # stacked (layers, E, ...): layers replicates, experts shards
        assert tuple(spec)[1] == "model", (name, spec)
    assert "model" not in _axes_of(_leaf_by_name(specs, "router"))
    assert "model" not in _axes_of(_leaf_by_name(specs, "wq"))
    assert EP_VERIFY_SIGS  # the whitelist is the contract


def test_tp_wrapper_matches_mp_tensor_only(moe_model):
    """tp_param_pspecs is mp_param_pspecs(tensor=True, expert=False) —
    the PR 7 layout is a stable special case, leaf for leaf."""
    dc, _, boxed, _ = moe_model
    a = tp_param_pspecs(boxed, _FakeMesh(2))
    b = mp_param_pspecs(boxed, _FakeMesh(2), tensor=True, expert=False)
    flat_a = jax.tree_util.tree_leaves(
        a, is_leaf=lambda x: isinstance(x, P))
    flat_b = jax.tree_util.tree_leaves(
        b, is_leaf=lambda x: isinstance(x, P))
    assert flat_a == flat_b


def test_nondividing_expert_axis_warns_once(moe_model, caplog):
    """E=8 on a 3-way axis replicates the expert stacks — with ONE
    repro.serving WARNING naming the leaf and the axis size, not silence
    (and not a warning per call)."""
    dc, _, boxed, _ = moe_model
    with caplog.at_level(logging.WARNING, logger="repro.serving"):
        specs = mp_param_pspecs(boxed, _FakeMesh(3), tensor=False,
                                expert=True)
    assert "model" not in _axes_of(_leaf_by_name(specs, "w_gate"))
    hits = [r for r in caplog.records
            if "w_gate" in r.getMessage() and "3-way" in r.getMessage()]
    assert len(hits) == 1, [r.getMessage() for r in caplog.records]
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="repro.serving"):
        mp_param_pspecs(boxed, _FakeMesh(3), tensor=False, expert=True)
    assert not [r for r in caplog.records if "w_gate" in r.getMessage()]


def test_mp_collective_payloads_split_by_kind(moe_model):
    """TP+EP at mp=2: per MoE layer 2 all_to_all exchanges + 1 psum
    combine, plus the TP wo psum — and the EP+SP composition swaps the
    per-layer psums for the single output re-replication."""
    dc, params, boxed, _ = moe_model
    L, d = dc.seq_len, dc.backbone.d_model
    n_layers = dc.backbone.n_layers
    E, k, cf = (dc.backbone.n_experts, dc.backbone.top_k,
                dc.backbone.capacity_factor)
    cap = min(int(max(1, -(-k * (L // 2) * cf // E))), L // 2)

    specs = mp_param_pspecs(boxed, _FakeMesh(2), tensor=True, expert=True)
    pay = mp_collective_payloads(params, specs, dc, mp_size=2)
    # wo psum + EP combine psum, per layer
    assert pay["psum"] == [L * d * 4] * (2 * n_layers)
    assert pay["all_to_all"] == [E * cap * d * 4] * (2 * n_layers)

    specs_sp = mp_param_pspecs(boxed, _FakeMesh(2), tensor=False,
                               expert=True)
    pay_sp = mp_collective_payloads(params, specs_sp, dc,
                                    mp_size=2, sp_size=2)
    assert pay_sp["psum"] == [L * dc.d_data * 4]  # x0 re-replication only
    hd = dc.backbone.resolved_head_dim
    sp_x = (L // 2) * dc.backbone.n_heads * hd * 4
    assert sorted(pay_sp["all_to_all"]) == sorted(
        [sp_x] * (4 * n_layers) + [E * cap * d * 4] * (2 * n_layers))


def test_sp_compatible_rules(moe_model):
    dc = paper_diffusion_policy_smoke()
    assert sp_compatible(dc, 1) == (True, "sp_size <= 1 (no sequence sharding)")
    assert sp_compatible(dc, 2)[0] and sp_compatible(dc, 4)[0]
    ok, reason = sp_compatible(dc, 3)
    assert not ok and "n_heads" in reason
    ok, reason = sp_compatible(dc, 8)  # heads=4 < 8
    assert not ok


# -- function-level parity ---------------------------------------------------


@needs2
def test_moe_ep_matches_dense_and_is_deterministic(moe_model):
    """EP dispatch (slice tokens -> route -> all_to_all -> local experts ->
    all_to_all back -> psum combine) vs the dense path, params actually
    sharded at E/mp per device."""
    dc, params, _, _ = moe_model
    cfg = dc.backbone
    # pull one layer row of the stacked moe params
    moe_params = jax.tree_util.tree_map(
        lambda l: l[0], params["decoder"]["g0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, dc.seq_len, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = moe_lib.moe_apply(moe_params, x, cfg)

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    pspecs = jax.tree_util.tree_map(
        lambda l: P("model", None, None) if l.ndim == 3 else P(None, None),
        moe_params)
    placed = jax.device_put(
        moe_params,
        jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs))
    assert (placed["w_gate"].addressable_shards[0].data.shape[0]
            == cfg.n_experts // 2)

    def ep_fn(p, x):
        y, aux = moe_lib.moe_apply(p, x, cfg, ep_axis="model")
        return y, aux["moe_aux_loss"]

    f = jax.jit(jax.shard_map(
        ep_fn, mesh=mesh, in_specs=(pspecs, P()), out_specs=(P(), P()),
        check_vma=False))
    y1, aux1 = f(placed, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux1), float(aux_ref["moe_aux_loss"]),
                               rtol=1e-6)
    y2, _ = f(placed, x)
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    # non-dividing L: the exchange-free fallback (full routing, local
    # expert block, psum) must hold the same parity
    x7 = jax.random.normal(jax.random.PRNGKey(2), (2, 7, cfg.d_model),
                           jnp.float32)
    y_ref7, _ = moe_lib.moe_apply(moe_params, x7, cfg)
    y7, _ = f(placed, x7)
    np.testing.assert_allclose(np.asarray(y7), np.asarray(y_ref7),
                               rtol=1e-5, atol=1e-5)

    # seq-sharded (Ulysses-composed) variant: x enters pre-sliced, the
    # output stays local — no slice, no combine psum
    g = jax.jit(jax.shard_map(
        lambda p, x: moe_lib.moe_apply(p, x, cfg, ep_axis="model",
                                       seq_sharded=True)[0],
        mesh=mesh, in_specs=(pspecs, P(None, "model", None)),
        out_specs=P(None, "model", None), check_vma=False))
    y_sp = g(placed, x)
    np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@needs2
def test_sp_denoiser_matches_replicated(sp_model):
    """Ulysses sp=2 through denoiser_fwd vs the plain forward; sp_size=1
    through the same call path is bit-identical (it IS the same program)."""
    dc, params, _, _ = sp_model
    t = jnp.array([3.0, 5.0], jnp.float32)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, dc.seq_len, dc.d_data),
                          jnp.float32)
    ref = denoiser_fwd(params, t, y, dc)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    rep = jax.tree_util.tree_map(lambda _: P(), params)
    f = jax.jit(jax.shard_map(
        lambda p, t, y: denoiser_fwd(p, t, y, dc, sp_axis="model", sp_size=2),
        mesh=mesh, in_specs=(rep, P(), P()), out_specs=P(),
        check_vma=False))
    out1 = f(params, t, y)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    out2 = f(params, t, y)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    g = jax.jit(jax.shard_map(
        lambda p, t, y: denoiser_fwd(p, t, y, dc, sp_axis=None, sp_size=1),
        mesh=mesh, in_specs=(rep, P(), P()), out_specs=P(),
        check_vma=False))
    np.testing.assert_array_equal(np.asarray(g(params, t, y)),
                                  np.asarray(ref))


# -- per-kind collective calibration -----------------------------------------


@needs2
def test_measure_collective_kinds():
    mesh = serving_mesh(1, 2)
    assert measure_collective_seconds(mesh, [4096], kind="psum") > 0.0
    assert measure_collective_seconds(mesh, [4096], kind="all_to_all") > 0.0
    # a layer stack's schedule: repeated sizes share one looped buffer
    for kind in ("psum", "all_to_all"):
        assert measure_collective_seconds(
            mesh, [4096] * 6 + [8192] * 2, kind=kind) > 0.0
    with pytest.raises(ValueError):
        measure_collective_seconds(mesh, [4096], kind="all_gather")
    by_kind = measure_collective_seconds_by_kind(
        mesh, {"psum": [4096], "all_to_all": [4096], "empty": []})
    assert set(by_kind) == {"psum", "all_to_all"}  # empty kinds dropped
    assert all(v > 0.0 for v in by_kind.values())


# -- engine-level parity -----------------------------------------------------


@pytest.fixture(scope="module")
def replicated_moe_ref(moe_model):
    dc, params, _, sched = moe_model
    eng = _engine(dc, params, sched)
    out = eng.serve(_requests(dc, 6))
    return out, eng.stats


@needs2
def test_ep1_bit_identical_to_tp_path(moe_model, replicated_moe_ref):
    """ep off at mp=2 builds the exact PR 7 TP program: samples bitwise
    against the legacy tp_param_pspecs construction, in both dispatch
    shapes (per-shard here; fused needs 4 devices, below)."""
    dc, params, boxed, sched = moe_model
    legacy = _engine(dc, params, sched, mp=2, boxed=boxed, shards=1,
                     dispatch="per-shard", legacy_tp=True)
    out_legacy = legacy.serve(_requests(dc, 6))
    eng = _engine(dc, params, sched, mp=2, boxed=boxed, shards=1,
                  dispatch="per-shard")
    out = eng.serve(_requests(dc, 6))
    for rid in out_legacy:
        np.testing.assert_array_equal(out[rid], out_legacy[rid])


@needs2
def test_ep2_matches_replicated_with_sharded_experts(moe_model,
                                                     replicated_moe_ref):
    """ep on at mp=2: expert stacks at E/mp per device (asserted on placed
    shard shapes), samples allclose vs the replicated engine, run-twice
    bitwise, per-kind collective lanes populated."""
    dc, params, boxed, sched = moe_model
    ref_out, ref_stats = replicated_moe_ref
    eng = _engine(dc, params, sched, mp=2, boxed=boxed, ep=True, shards=1,
                  dispatch="per-shard")
    placed = eng.workers[0]._params
    wg = _leaf_by_name(placed, "w_gate")
    local = wg.addressable_shards[0].data.shape
    assert local[1] == dc.backbone.n_experts // 2, (local, wg.shape)
    assert np.prod(local) == np.prod(wg.shape) // 2  # 1/mp bytes
    out1 = eng.serve(_requests(dc, 6))
    for rid in ref_out:
        np.testing.assert_allclose(
            out1[rid], ref_out[rid], rtol=1e-5, atol=1e-5)
    assert eng.stats.retired == ref_stats.retired
    s = eng.stats
    assert s.collective_psum_s > 0.0 and s.collective_a2a_s > 0.0
    np.testing.assert_allclose(
        s.collective_psum_s + s.collective_a2a_s, s.collective_s, rtol=1e-9)
    tb = s.timing_breakdown()
    assert tb["collective_a2a_frac"] > 0.0
    eng2 = _engine(dc, params, sched, mp=2, boxed=boxed, ep=True, shards=1,
                   dispatch="per-shard")
    eng2.adopt_programs(eng)
    out2 = eng2.serve(_requests(dc, 6))
    for rid in out1:
        np.testing.assert_array_equal(out1[rid], out2[rid])


@needs4
def test_ep2_fused_dispatch_parity(moe_model, replicated_moe_ref):
    """Fused dispatch at shards=2 x mp=2 with expert parallelism: allclose
    parity and an unchanged superstep count — EP rides inside the one
    program like TP does."""
    dc, params, boxed, sched = moe_model
    ref_out, _ = replicated_moe_ref
    base = _engine(dc, params, sched, shards=2, dispatch="fused")
    out_b = base.serve(_requests(dc, 6))
    eng = _engine(dc, params, sched, mp=2, boxed=boxed, ep=True, shards=2,
                  dispatch="fused")
    out = eng.serve(_requests(dc, 6))
    for rid in ref_out:
        np.testing.assert_allclose(
            out[rid], ref_out[rid], rtol=1e-5, atol=1e-5)
    assert eng.stats.supersteps == base.stats.supersteps
    assert out_b.keys() == out.keys()


@pytest.fixture(scope="module")
def replicated_sp_ref(sp_model):
    dc, params, _, sched = sp_model
    eng = _engine(dc, params, sched)
    out = eng.serve(_requests(dc, 6))
    return out, eng.stats


@needs2
def test_sp2_engine_matches_replicated(sp_model, replicated_sp_ref):
    """Engine-level Ulysses: sp=2 model groups (replicated weights,
    sequence-sharded stream) reproduce the replicated samples within
    allclose and are run-twice deterministic."""
    dc, params, boxed, sched = sp_model
    ref_out, ref_stats = replicated_sp_ref
    eng = _engine(dc, params, sched, mp=2, boxed=boxed, sp=2, shards=1,
                  dispatch="per-shard")
    # SP shards no params: every placed leaf stays whole per device
    placed = eng.workers[0]._params
    wq = _leaf_by_name(placed, "wq")
    assert wq.addressable_shards[0].data.shape == wq.shape
    out1 = eng.serve(_requests(dc, 6))
    for rid in ref_out:
        np.testing.assert_allclose(
            out1[rid], ref_out[rid], rtol=1e-5, atol=1e-5)
    assert eng.stats.retired == ref_stats.retired
    assert eng.stats.collective_a2a_s > 0.0
    eng2 = _engine(dc, params, sched, mp=2, boxed=boxed, sp=2, shards=1,
                   dispatch="per-shard")
    eng2.adopt_programs(eng)
    out2 = eng2.serve(_requests(dc, 6))
    for rid in out1:
        np.testing.assert_array_equal(out1[rid], out2[rid])
