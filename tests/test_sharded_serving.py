"""Sharded serving: worker extraction, routing, shard-axis packing, budget
tiers, overcommit, and cross-shard-count parity.

The exactness spine: a chain's trajectory depends only on its own
``ASDChainState`` (per-request key), so routing/sharding — pure host-side
scheduling — can never move a sample's bits.  ``ShardedASDEngine(shards=1)``
must match ``ContinuousASDEngine`` per ``ASDChainState`` LEAF (same worker
core, same loop), and shards=2/4 must reproduce the single-shard samples and
speculation counters per request whenever grants equal demands (unpacked, or
packed at covering budgets).

Multi-device specifics (shard_map over a ``slots`` mesh) skip on a
single-device install; CI runs them under
``XLA_FLAGS=--xla_force_host_platform_device_count``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import AcceptRateTheta, StaticTheta
from repro.distributed.sharding import (
    shard_placements,
    shard_pspecs,
    slots_mesh,
)
from repro.serving.engine import ContinuousASDEngine, Request
from repro.serving.metrics import EngineStats, RequestMetrics
from repro.serving.packing import (
    WaterfillingAllocator,
    build_pack_maps,
    build_sharded_pack_maps,
    make_allocator,
    packed_superstep,
    sharded_packed_superstep,
)
from repro.serving.router import (
    ROUTERS,
    DeadlineAware,
    LeastLoaded,
    RoundRobin,
    make_router,
)
from repro.serving.scheduler import AdmissionContext, BudgetAware
from repro.serving.sharded import ShardedASDEngine
from repro.serving.worker import ShardWorker

THETA = 5


def _requests(n, seed0=100, **kw):
    return [
        Request(i, key=jax.random.PRNGKey(seed0 + i),
                y0=np.zeros((2,), np.float32), **kw)
        for i in range(n)
    ]


def _continuous(sl_model2, sched_tiny, **kw):
    base = dict(schedule=sched_tiny, event_shape=(2,), num_slots=4,
                theta=THETA, eager_head=True, keep_trajectory=True)
    base.update(kw)
    return ContinuousASDEngine(lambda cond: sl_model2, **base)


def _sharded(sl_model2, sched_tiny, **kw):
    base = dict(schedule=sched_tiny, event_shape=(2,), num_slots=4,
                theta=THETA, eager_head=True, keep_trajectory=True)
    base.update(kw)
    return ShardedASDEngine(lambda cond: sl_model2, **base)


@pytest.fixture(scope="module")
def warm_single(sl_model2, sched_tiny):
    eng = _continuous(sl_model2, sched_tiny)
    eng.serve(_requests(2, seed0=10**6))
    return eng


@pytest.fixture(scope="module")
def single_ref(warm_single, sl_model2, sched_tiny):
    """Reference single-shard serve of 9 requests: samples + counters."""
    eng = _continuous(sl_model2, sched_tiny).adopt_programs(warm_single)
    out = eng.serve(_requests(9))
    return out, {m.rid: m for m in eng.stats.per_request}


def _assert_counters_match(stats, ref_metrics):
    for m in stats.per_request:
        r = ref_metrics[m.rid]
        assert (m.rounds, m.head_calls, m.model_evals, m.accepts,
                m.proposals) == (r.rounds, r.head_calls, r.model_evals,
                                 r.accepts, r.proposals), m.rid


# ---------------------------------------------------------------------------
# shards=1 == ContinuousASDEngine, per ASDChainState leaf
# ---------------------------------------------------------------------------


def test_shards1_bitwise_parity_per_leaf(warm_single, sl_model2, sched_tiny,
                                         single_ref):
    """ShardedASDEngine(shards=1) is the SAME engine: identical samples,
    identical per-request counters, and — stepped boundary by boundary —
    identical ``ASDChainState`` leaves on every superstep."""
    ref_out, ref_m = single_ref
    sh = _sharded(sl_model2, sched_tiny, shards=1).adopt_programs(warm_single)
    out = sh.serve(_requests(9))
    assert sorted(out) == sorted(ref_out)
    for rid in ref_out:
        np.testing.assert_array_equal(out[rid], ref_out[rid])
    _assert_counters_match(sh.stats, ref_m)

    # boundary-by-boundary leaf parity under the step() drive
    eng = _continuous(sl_model2, sched_tiny).adopt_programs(warm_single)
    sh = _sharded(sl_model2, sched_tiny, shards=1).adopt_programs(warm_single)
    for r in _requests(7, seed0=400):
        eng.submit(r)
    for r in _requests(7, seed0=400):
        sh.submit(r)
    more_a, more_b = True, True
    while more_a or more_b:
        more_a, more_b = eng.step(), sh.step()
        assert more_a == more_b
        for la, lb in zip(
            jax.tree_util.tree_leaves(eng._states),
            jax.tree_util.tree_leaves(sh.workers[0]._states),
        ):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("shards", [2, 4])
def test_multi_shard_sample_and_counter_parity(warm_single, sl_model2,
                                               sched_tiny, single_ref, shards):
    """shards=2/4 on the identical request stream serve bit-identical
    samples and identical per-chain speculation counters: sharding is
    scheduling, not sampling."""
    ref_out, ref_m = single_ref
    sh = _sharded(sl_model2, sched_tiny, shards=shards,
                  router=make_router("round-robin"))
    out = sh.serve(_requests(9))
    assert sorted(out) == sorted(ref_out)
    for rid in ref_out:
        np.testing.assert_array_equal(out[rid], ref_out[rid])
    _assert_counters_match(sh.stats, ref_m)
    # the router actually spread the stream
    assert (sh.routed_counts > 0).all()


def test_multi_shard_packed_covering_budget_parity(sl_model2, sched_tiny):
    """Packed execution at covering per-shard budgets: grants == demands on
    every shard, so 2-shard packed serving reproduces the 1-shard packed
    samples bit for bit (an adaptive controller keeps windows moving)."""
    kw = dict(execution="packed",
              controller=AcceptRateTheta(theta_min=1),
              allocator=WaterfillingAllocator(theta_max=THETA))
    # covering is PER SHAPE: 4 slots x theta for the single shard, 2 slots
    # x theta per shard for the pair — grants == demands on both, so the
    # budget never bends a window and the bits must agree
    ref = _sharded(sl_model2, sched_tiny, shards=1,
                   round_budget=4 * THETA, **kw)
    ref_out = ref.serve(_requests(9))
    sh = _sharded(sl_model2, sched_tiny, shards=2, round_budget=2 * THETA,
                  router=make_router("round-robin"), **kw)
    out = sh.serve(_requests(9))
    for rid in ref_out:
        np.testing.assert_array_equal(out[rid], ref_out[rid])
    ref_m = {m.rid: m for m in ref.stats.per_request}
    _assert_counters_match(sh.stats, ref_m)


# ---------------------------------------------------------------------------
# routers
# ---------------------------------------------------------------------------


class _StubWorker:
    def __init__(self, load, free=1):
        self.load = load
        self.scheduler = type("S", (), {"free_slots": lambda s: [0] * free,
                                        "queue_depth": 0})()


def test_round_robin_cycles():
    r = RoundRobin()
    ws = [_StubWorker(0.0) for _ in range(3)]
    assert [r.route(Request(i), ws) for i in range(7)] == [
        0, 1, 2, 0, 1, 2, 0]


def test_least_loaded_picks_min_and_breaks_ties_low():
    r = LeastLoaded()
    assert r.route(Request(0), [_StubWorker(0.5), _StubWorker(0.25),
                                _StubWorker(0.25)]) == 1
    assert r.route(Request(0), [_StubWorker(0.1), _StubWorker(0.1)]) == 0


def test_deadline_router_reserves_headroom():
    r = DeadlineAware()
    ws = [_StubWorker(0.2), _StubWorker(0.8), _StubWorker(1.5)]
    # deadline traffic -> least loaded; best-effort -> busiest unsaturated
    assert r.route(Request(0, deadline=1.0), ws) == 0
    assert r.route(Request(0), ws) == 1
    # everything saturated: best effort falls back to least loaded
    ws = [_StubWorker(1.2), _StubWorker(1.5)]
    assert r.route(Request(0), ws) == 0


def test_least_loaded_balances_skewed_stream(sl_model2, sched_tiny):
    """A burst routed by least-loaded lands evenly across shards even
    though every request arrives before any slot frees: queue depth is part
    of the load signal."""
    sh = _sharded(sl_model2, sched_tiny, shards=2)  # default LeastLoaded
    out = sh.serve(_requests(12))
    assert len(out) == 12
    counts = sh.routed_counts
    assert counts.sum() == 12
    assert abs(int(counts[0]) - int(counts[1])) <= 1
    # both shards actually retired work
    assert all(s.retired > 0 for s in sh.shard_stats)


def test_make_router_names():
    for name in ROUTERS:
        assert make_router(name).name == name
    with pytest.raises(ValueError):
        make_router("nope")


# ---------------------------------------------------------------------------
# shard-axis packing: maps and allocators never cross shard boundaries
# ---------------------------------------------------------------------------


def test_sharded_pack_maps_are_shard_local():
    """Every packed position's slot_id stays inside ITS shard's [0, S_local)
    range whatever the grant mix — the no-cross-shard-gather contract."""
    rng = np.random.default_rng(0)
    nsh, S_local, theta, budget = 4, 3, 6, 10
    for _ in range(25):
        grants = rng.integers(0, theta + 1, size=(nsh, S_local))
        # keep each shard inside its budget
        for s in range(nsh):
            while grants[s].sum() > budget:
                grants[s][rng.integers(S_local)] = max(
                    0, grants[s][rng.integers(S_local)] - 1)
        maps = build_sharded_pack_maps(jnp.asarray(grants, jnp.int32), budget)
        slot_id = np.asarray(maps.slot_id)
        valid = np.asarray(maps.valid)
        assert slot_id.shape == (nsh, budget)
        assert (slot_id >= 0).all() and (slot_id < S_local).all()
        for s in range(nsh):
            # per-shard maps equal the unsharded builder on that shard's row
            ref = build_pack_maps(jnp.asarray(grants[s], jnp.int32), budget)
            np.testing.assert_array_equal(slot_id[s], np.asarray(ref.slot_id))
            np.testing.assert_array_equal(valid[s], np.asarray(ref.valid))
            assert valid[s].sum() == grants[s].sum()


def test_allocate_sharded_is_per_shard_independent():
    """allocate_sharded == stacked per-shard allocate, with per-shard
    budgets honored independently (rebalancing one shard's tier cannot move
    another shard's grants)."""
    rng = np.random.default_rng(1)
    nsh, S_local, theta = 3, 4, 6
    alloc = make_allocator("waterfill", theta_max=theta)
    demand = jnp.asarray(rng.integers(0, theta + 1, size=(nsh, S_local)),
                         jnp.int32)
    budgets = jnp.asarray([4, 9, 24], jnp.int32)
    weights = jnp.ones((nsh, S_local), jnp.float32)
    grants = np.asarray(alloc.allocate_sharded(demand, budgets, weights))
    for s in range(nsh):
        ref = np.asarray(alloc.allocate(demand[s], budgets[s], weights[s]))
        np.testing.assert_array_equal(grants[s], ref)
        assert grants[s].sum() <= int(budgets[s])
        assert (grants[s] <= np.asarray(demand[s])).all()
    # ample shard grants demand exactly (the bit-exactness precondition)
    np.testing.assert_array_equal(grants[2], np.asarray(demand[2]))


# ---------------------------------------------------------------------------
# budget auto-tiering
# ---------------------------------------------------------------------------


def test_budget_tier_ladder_and_hysteresis(sl_model2, sched_tiny):
    eng = _continuous(sl_model2, sched_tiny, execution="packed",
                      round_budget="auto",
                      controller=AcceptRateTheta(theta_min=1))
    ladder = eng._budget_ladder
    # pow2 rungs, except the top tier is capped at the exact covering
    # budget (padding the packed call past any possible demand buys nothing)
    assert all(t & (t - 1) == 0 for t in ladder[:-1])
    assert ladder[0] >= min(eng.num_slots, ladder[-1])
    assert ladder[-1] == eng.num_slots * THETA
    assert eng.round_budget == ladder[-1]  # opens covering

    # upshift is immediate: demand above the current tier jumps straight up
    eng.round_budget = ladder[0]
    eng._demand_ewma = float(ladder[-1])
    assert eng._pick_budget() == ladder[-1]

    # downshift: one rung, and only once demand clears the hysteresis band
    eng.round_budget = ladder[-1]
    lower = ladder[-2]
    eng._demand_ewma = 0.9 * lower  # inside the band: hold the tier
    assert eng._pick_budget() == ladder[-1]
    eng._demand_ewma = 0.5 * lower  # comfortably below: drop one rung
    assert eng._pick_budget() == lower
    # never below the floor tier
    eng.round_budget = ladder[0]
    eng._demand_ewma = 0.0
    assert eng._pick_budget() == ladder[0]


def test_auto_budget_unpins_after_burst_drains(sl_model2, sched_tiny):
    """Regression: after a burst fully drains no further harvests run, so
    the demand EWMA FROZE at the burst's level and pinned the auto tier at
    the top rung — the first trickle after an idle gap paid burst-sized
    supersteps indefinitely.  The drained boundary must reset the signal,
    and a following trickle must walk the tier down within the hysteresis
    schedule (one rung per boundary)."""
    eng = _continuous(sl_model2, sched_tiny, execution="packed",
                      round_budget="auto",
                      controller=AcceptRateTheta(theta_min=1))
    ladder = eng._budget_ladder
    eng.serve(_requests(12))  # burst: demand saturates the slots
    # the idle boundary cleared the pressure signal (it used to hold the
    # last blended demand with nothing left to decay it)
    assert eng._demand_ewma == 0.0 and eng._live_demand == 0

    # burst -> trickle: with the tier parked at the top rung, one lone
    # chain must pull it below the burst tier, not inherit it
    eng.round_budget = ladder[-1]
    eng.serve(_requests(1, seed0=999))
    assert eng.round_budget < ladder[-1]
    assert eng._demand_ewma == 0.0  # trickle drained -> reset again


def test_budget_auto_engine_serves_and_bounds_cache(sl_model2, sched_tiny):
    """An auto-budget engine serves correct work and compiles at most one
    executable per (R, tier) pair — the ladder keeps the cache O(log)."""
    eng = _continuous(sl_model2, sched_tiny, execution="packed",
                      round_budget="auto",
                      controller=AcceptRateTheta(theta_min=1))
    out = eng.serve(_requests(11))
    assert sorted(out) == list(range(11))
    ladder = set(eng._budget_ladder)
    assert {b for (_, b) in eng._superstep_fns} <= ladder
    assert len(eng._superstep_fns) <= len(ladder)
    # the tier tracked demand: after the drain it sits at or below covering
    assert eng.round_budget in ladder


def test_budget_auto_requires_packed(sl_model2, sched_tiny):
    with pytest.raises(ValueError):
        _continuous(sl_model2, sched_tiny, round_budget="auto")


# ---------------------------------------------------------------------------
# slot overcommit
# ---------------------------------------------------------------------------


def test_budget_aware_quota_respects_overcommit():
    pol = BudgetAware()
    ctx = AdmissionContext(K=16, theta_max=4, round_budget=8, live_demand=8,
                           theta_open=4)
    # saturated budget, no overcommit: defer everything
    assert pol.admit_quota(4, ctx) == 0
    # overcommit 2x: headroom for (2*8 - 8) / theta_open = 2 more chains
    ctx.overcommit = 2.0
    assert pol.admit_quota(4, ctx) == 2
    ctx.overcommit = 4.0
    assert pol.admit_quota(4, ctx) == 4  # capped by free slots


def test_overcommit_engine_multiplexes_past_nominal(sl_model2, sched_tiny):
    """num_slots exceeds round_budget // theta_max: without overcommit the
    BudgetAware policy holds concurrency near the budget's nominal chain
    count; with overcommit the allocator multiplexes more admitted chains
    over the same budget (and the samples still drain correctly)."""
    def run(overcommit):
        eng = _continuous(
            sl_model2, sched_tiny, num_slots=6, execution="packed",
            round_budget=2 * THETA,  # nominal full-width concurrency: 2
            policy=BudgetAware(), overcommit=overcommit,
        )
        peak = 0
        for r in _requests(10, seed0=700):
            eng.submit(r)
        while eng.step():
            peak = max(peak, len(eng.scheduler.active_slots()))
        out = eng.drain_results()
        assert sorted(out) == list(range(10))
        return peak

    nominal = (2 * THETA) // THETA
    assert run(1.0) <= nominal + 1  # the +1: idle-engine always-admit floor
    assert run(3.0) > nominal + 1  # multiplexed concurrency

    with pytest.raises(ValueError):
        _continuous(sl_model2, sched_tiny, overcommit=0.5)


# ---------------------------------------------------------------------------
# per-shard EngineStats and the merged view
# ---------------------------------------------------------------------------


def test_engine_stats_merged_sums_consistent():
    a = EngineStats(shard=0, requests=3, retired=3, rounds_total=10,
                    supersteps=5, dispatch_s=0.25, device_s=1.0,
                    host_sync_s=0.5, accepts_total=7, proposals_total=9,
                    wall_time=2.0)
    b = EngineStats(shard=1, requests=2, retired=1, rounds_total=4,
                    supersteps=2, dispatch_s=0.5, device_s=0.25,
                    host_sync_s=0.25, accepts_total=3, proposals_total=8,
                    wall_time=1.5)
    a.per_request.append(RequestMetrics(
        rid=0, queue_latency=0.1, service_time=0.2, rounds=4, head_calls=2,
        model_evals=8, accepts=3, proposals=4))
    m = EngineStats.merged([a, b], wall_time=2.5)
    assert (m.requests, m.retired, m.rounds_total, m.supersteps) == (5, 4, 14, 7)
    assert m.accepts_total == 10 and m.proposals_total == 17
    assert m.wall_time == 2.5 and m.shard is None
    assert len(m.per_request) == 1
    t = m.timing_breakdown()
    for f in ("dispatch_s", "device_s", "host_sync_s"):
        assert t[f] == pytest.approx(
            getattr(a, f) + getattr(b, f)), f
    # default wall: max over shards (concurrent walls must not add)
    assert EngineStats.merged([a, b]).wall_time == 2.0


def test_sharded_engine_merged_stats(sl_model2, sched_tiny):
    sh = _sharded(sl_model2, sched_tiny, shards=2,
                  router=make_router("round-robin"))
    out = sh.serve(_requests(8))
    assert len(out) == 8
    per = sh.shard_stats
    assert [s.shard for s in per] == [0, 1]
    merged = sh.stats
    assert merged.retired == sum(s.retired for s in per) == 8
    assert merged.rounds_total == sum(s.rounds_total for s in per)
    assert merged.supersteps == sum(s.supersteps for s in per)
    t = merged.timing_breakdown()
    for f in ("dispatch_s", "device_s", "host_sync_s"):
        assert t[f] == pytest.approx(sum(getattr(s, f) for s in per))
    assert merged.wall_time > 0.0  # the front end's single wall clock
    assert len(merged.per_request) == 8


# ---------------------------------------------------------------------------
# mesh plumbing: slots mesh, shard placements, shard_map superstep
# ---------------------------------------------------------------------------


def test_shard_placements_wraps_devices():
    devs = jax.devices()
    places = shard_placements(2 * len(devs) + 1)
    assert len(places) == 2 * len(devs) + 1
    assert places[0] == devs[0] and places[len(devs)] == devs[0]


def test_slots_mesh_single_device():
    mesh = slots_mesh(1)
    assert mesh.axis_names == ("slots",)
    sh = shard_pspecs(mesh)
    assert sh.spec == jax.sharding.PartitionSpec("slots")


def test_slots_mesh_rejects_oversubscription():
    with pytest.raises(ValueError):
        slots_mesh(len(jax.devices()) + 1)


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (set XLA_FLAGS="
                           "--xla_force_host_platform_device_count)")
def test_sharded_packed_superstep_matches_per_shard_loop(sl_model2,
                                                         sched_tiny):
    """The shard_map-driven stacked superstep is bit-identical to looping
    packed_superstep shard by shard — and, being manual-mode SPMD with no
    collectives, provably cannot gather across shards."""
    from repro.core.asd import init_chain_state

    nsh, S_local, theta = 2, 3, 4
    ctrl = StaticTheta()
    budget = S_local * theta

    def shard_states(seed):
        return jax.vmap(
            lambda k: init_chain_state(
                sched_tiny, jnp.zeros((2,)), k, theta, "buffer", True, ctrl)
        )(jax.random.split(jax.random.PRNGKey(seed), S_local))

    stacked = jax.tree_util.tree_map(
        lambda *x: jnp.stack(x), *[shard_states(s) for s in range(nsh)])
    weights = jnp.ones((nsh, S_local))
    mesh = slots_mesh(nsh)
    stacked = jax.device_put(stacked, shard_pspecs(mesh, stacked))
    make_fn = lambda p, cond: sl_model2
    alloc = WaterfillingAllocator(theta_max=theta)
    kw = dict(rounds=3, theta=theta, budget=budget, allocator=alloc,
              keep_trajectory=True)
    out = sharded_packed_superstep(
        make_fn, None, sched_tiny, stacked, None, weights, mesh=mesh, **kw)
    refs = [
        packed_superstep(
            make_fn, None, sched_tiny,
            jax.tree_util.tree_map(lambda x: x[s], stacked), None,
            weights[s], **kw)
        for s in range(nsh)
    ]
    ref = jax.tree_util.tree_map(lambda *x: jnp.stack(x), *refs)
    for la, lb in zip(jax.tree_util.tree_leaves(out),
                      jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (set XLA_FLAGS="
                           "--xla_force_host_platform_device_count)")
def test_fused_dispatch_parity(warm_single, sl_model2, sched_tiny,
                               single_ref):
    """dispatch="fused" (one shard_map program over the slots mesh) serves
    the exact per-shard-dispatch — and single-shard — bits, unpacked and
    packed, and merges stats consistently."""
    ref_out, ref_m = single_ref
    for kw in (dict(),
               dict(execution="packed", round_budget=2 * THETA,
                    allocator=WaterfillingAllocator(theta_max=THETA))):
        sh = _sharded(sl_model2, sched_tiny, shards=2, dispatch="fused",
                      router=make_router("round-robin"), **kw)
        out = sh.serve(_requests(9))
        for rid in ref_out:
            np.testing.assert_array_equal(out[rid], ref_out[rid])
        _assert_counters_match(sh.stats, ref_m)
        assert sh.stats.retired == 9
    # fused + per-shard budget tiers is a contradiction: one program
    with pytest.raises(ValueError):
        _sharded(sl_model2, sched_tiny, shards=2, dispatch="fused",
                 execution="packed", round_budget="auto")


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (set XLA_FLAGS="
                           "--xla_force_host_platform_device_count)")
def test_fused_step_drive(sl_model2, sched_tiny):
    """The step() drive works in fused mode (open-loop use)."""
    sh = _sharded(sl_model2, sched_tiny, shards=2, dispatch="fused",
                  router=make_router("round-robin"))
    for r in _requests(5, seed0=900):
        sh.submit(r)
    while sh.step():
        pass
    out = sh.drain_results()
    assert sorted(out) == list(range(5))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (set XLA_FLAGS="
                           "--xla_force_host_platform_device_count)")
def test_sharded_engine_on_devices_parity(warm_single, sl_model2, sched_tiny,
                                          single_ref):
    """Workers pinned to distinct (simulated) devices still serve the exact
    single-shard bits: placement is topology, not semantics."""
    ref_out, ref_m = single_ref
    sh = _sharded(sl_model2, sched_tiny, shards=2,
                  devices=shard_placements(2),
                  router=make_router("round-robin"))
    assert sh.workers[0].device != sh.workers[1].device
    out = sh.serve(_requests(9))
    for rid in ref_out:
        np.testing.assert_array_equal(out[rid], ref_out[rid])
    _assert_counters_match(sh.stats, ref_m)


# ---------------------------------------------------------------------------
# engine-shape validation
# ---------------------------------------------------------------------------


def test_sharded_engine_validates_shapes(sl_model2, sched_tiny):
    with pytest.raises(ValueError):
        _sharded(sl_model2, sched_tiny, shards=3)  # 4 slots % 3 != 0
    with pytest.raises(ValueError):
        _sharded(sl_model2, sched_tiny, shards=0)


def test_run_rounds_single_helper():
    """The superstep body is ONE parameterized helper on the worker — the
    packed/unpacked duplication is gone."""
    import inspect

    from repro.serving import worker as worker_mod

    src = inspect.getsource(worker_mod)
    assert src.count("def _run_rounds") == 1
    assert "def _run_rounds" in inspect.getsource(ShardWorker._run_rounds)
