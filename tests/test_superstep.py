"""Device-resident supersteps: exactness edges, donation safety, and
compile behavior.

Contracts:

  1. BIT-EXACTNESS — ``asd_superstep(R)`` equals R sequential ``asd_round``
     calls per ``ASDChainState`` leaf (the pinned-seed golden), for Static /
     AIMD / AcceptRate controllers across ragged retire patterns, including
     R=1; ``packed_superstep`` likewise equals R sequential ``packed_round``
     calls at covering budgets.  Chains that retire mid-superstep become
     masked no-ops and keep every leaf (counters included) frozen.
  2. ENGINE PARITY — ``rounds_per_sync=R`` serves the same sample bits and
     per-request counters as the R=1 engine, for unpacked AND packed
     execution and for the auto ladder.
  3. DONATION SAFETY — the superstep donates the slot-state pytree; a new
     dispatch after a boundary harvest must work on the fresh buffers (no
     stale reuse), across consecutive serve() waves.
  4. ONE COMPILE PER (R, budget) — driving a superstep program across many
     boundaries and admission waves never recompiles it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AIMDTheta,
    AcceptRateTheta,
    StaticTheta,
    asd_round,
    asd_superstep,
    init_chain_state,
)
from repro.serving.engine import ContinuousASDEngine, Request
from repro.serving.packing import (
    WaterfillingAllocator,
    packed_round,
    packed_superstep,
)

THETA = 5
SLOTS = 4

CONTROLLERS = {
    "static": StaticTheta(),
    "aimd": AIMDTheta(backoff=0.5, theta_min=1),
    "accept-rate": AcceptRateTheta(theta_min=1),
}


def _slot_states(sched, controller, windows=None, seed=0, positions=None):
    states = jax.vmap(
        lambda k: init_chain_state(
            sched, jnp.zeros(2), k, THETA, "buffer", True, controller)
    )(jax.random.split(jax.random.PRNGKey(seed), SLOTS))
    if windows is not None:
        states = dataclasses.replace(
            states, theta_live=jnp.asarray(windows, jnp.int32))
    if positions is not None:
        states = dataclasses.replace(
            states, a=jnp.asarray(positions, jnp.int32))
    return states


def _assert_states_equal(a, b, msg=""):
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if x is None:
            assert y is None
            continue
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y), err_msg=f"{msg}: field {f.name}")


# ---------------------------------------------------------------------------
# core API: asd_superstep / packed_superstep vs sequential rounds
# ---------------------------------------------------------------------------


def test_superstep_r1_equals_asd_round(sl_model2, sched_tiny):
    """R=1 is exactly one asd_round per leaf — the degenerate superstep."""
    st = _slot_states(sched_tiny, StaticTheta(), seed=2)
    one = jax.jit(jax.vmap(lambda s: asd_round(
        sl_model2, sched_tiny, s, THETA, True, "buffer", True)))
    sup = jax.jit(jax.vmap(lambda s: asd_superstep(
        sl_model2, sched_tiny, s, THETA, rounds=1, eager_head=True)))
    _assert_states_equal(one(st), sup(st), "R=1")


@pytest.mark.parametrize("ctrl_name", sorted(CONTROLLERS))
@pytest.mark.parametrize("R", [2, 3, 5])
def test_superstep_matches_sequential_rounds(sl_model2, sched_tiny,
                                             ctrl_name, R):
    """asd_superstep(R) == R sequential asd_round calls, every leaf, driven
    to completion — chains retire at ragged rounds, so later supersteps mix
    live and frozen lanes (the masked-no-op edge)."""
    controller = CONTROLLERS[ctrl_name]
    # ragged starting positions: slot 3 is one commit from retiring, slot 2
    # mid-chain — retires land mid-superstep at different iterations
    st = _slot_states(sched_tiny, controller, windows=[1, 3, 5, 2],
                      positions=[0, 4, 9, 15], seed=7)
    K = sched_tiny.K
    seq = jax.jit(jax.vmap(lambda s: asd_round(
        sl_model2, sched_tiny, s, THETA, True, "buffer", True, "core",
        controller)))
    sup = jax.jit(jax.vmap(lambda s: asd_superstep(
        sl_model2, sched_tiny, s, THETA, rounds=R, eager_head=True,
        controller=controller)))
    su = sp = st
    for _ in range(12):
        for _ in range(R):
            su = seq(su)
        sp = sup(sp)
        _assert_states_equal(su, sp, f"{ctrl_name}/R={R}")
        if (np.asarray(su.a) >= K).all():
            break
    assert (np.asarray(su.a) >= K).all()  # exercised the all-retired tail


def test_packed_superstep_matches_sequential_packed_rounds(sl_model2,
                                                           sched_tiny):
    """packed_superstep(R) == R sequential packed_round calls at a covering
    budget (which also pins it to the unpacked superstep, by PR-3's
    packed == unpacked contract)."""
    controller = AcceptRateTheta(theta_min=1)
    st = _slot_states(sched_tiny, controller, windows=[1, 3, 5, 2], seed=3)
    R, budget = 3, SLOTS * THETA
    alloc = WaterfillingAllocator(theta_max=THETA)
    weights = jnp.ones((SLOTS,))
    kw = dict(theta=THETA, budget=budget, allocator=alloc, eager_head=True,
              noise_mode="buffer", keep_trajectory=True,
              controller=controller)
    seq = jax.jit(lambda ss, w: packed_round(
        lambda p, cond: sl_model2, None, sched_tiny, ss, None, w, **kw))
    sup = jax.jit(lambda ss, w: packed_superstep(
        lambda p, cond: sl_model2, None, sched_tiny, ss, None, w,
        rounds=R, **kw))
    su = sp = st
    for _ in range(6):
        for _ in range(R):
            su = seq(su, weights)
        sp = sup(sp, weights)
        _assert_states_equal(su, sp, f"packed R={R}")


def test_superstep_identity_when_all_retired(sl_model2, sched_tiny):
    """All slots retired: the superstep is a pure no-op scan — every leaf
    bit-identical, counters included."""
    K = sched_tiny.K
    st = _slot_states(sched_tiny, StaticTheta(), positions=[K] * SLOTS)
    out = jax.jit(jax.vmap(lambda s: asd_superstep(
        sl_model2, sched_tiny, s, THETA, rounds=4, eager_head=True)))(st)
    _assert_states_equal(st, out, "all-retired")


def test_mid_superstep_retire_freezes_state(sl_model2, sched_tiny):
    """A chain finishing inside the superstep keeps its committed state and
    counters frozen for the remaining scan iterations: one big superstep
    lands on the same fixed point as round-by-round driving."""
    controller = StaticTheta()
    st0 = jax.vmap(lambda k: init_chain_state(
        sched_tiny, jnp.zeros(2), k, THETA, "buffer", True, controller)
    )(jax.random.split(jax.random.PRNGKey(11), SLOTS))
    K = sched_tiny.K
    seq = jax.jit(jax.vmap(lambda s: asd_round(
        sl_model2, sched_tiny, s, THETA, True, "buffer", True)))
    # drive sequentially to the all-done fixed point
    su = st0
    for _ in range(40):
        su = seq(su)
        if (np.asarray(su.a) >= K).all():
            break
    assert (np.asarray(su.a) >= K).all()
    # one superstep big enough to cover every chain's full run + dead tail
    sp = jax.jit(jax.vmap(lambda s: asd_superstep(
        sl_model2, sched_tiny, s, THETA, rounds=40, eager_head=True)))(st0)
    _assert_states_equal(su, sp, "fixed-point")


# ---------------------------------------------------------------------------
# engine: rounds_per_sync parity, donation, compile caching
# ---------------------------------------------------------------------------


def _requests(n, seed0=100):
    return [Request(i, key=jax.random.PRNGKey(seed0 + i),
                    y0=np.zeros((2,), np.float32)) for i in range(n)]


def _engine(sl_model2, sched_tiny, **kw):
    base = dict(schedule=sched_tiny, event_shape=(2,), num_slots=SLOTS,
                theta=THETA, eager_head=True, keep_trajectory=True)
    base.update(kw)
    return ContinuousASDEngine(lambda cond: sl_model2, **base)


@pytest.mark.parametrize("execution", ["unpacked", "packed"])
@pytest.mark.parametrize("R", [2, 4])
def test_engine_rounds_per_sync_parity(sl_model2, sched_tiny, execution, R):
    """rounds_per_sync=R serves bit-identical samples AND identical
    per-request speculation counters to the R=1 engine (samples depend only
    on the request key, so boundary-quantized admission cannot move them)."""
    n = 9
    ref_eng = _engine(sl_model2, sched_tiny, execution=execution)
    ref = ref_eng.serve(_requests(n))
    eng = _engine(sl_model2, sched_tiny, execution=execution,
                  rounds_per_sync=R)
    out = eng.serve(_requests(n))
    assert sorted(out) == sorted(ref)
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])
    ref_m = {m.rid: m for m in ref_eng.stats.per_request}
    for m in eng.stats.per_request:
        r = ref_m[m.rid]
        assert (m.rounds, m.head_calls, m.model_evals, m.accepts,
                m.proposals) == (r.rounds, r.head_calls, r.model_evals,
                                 r.accepts, r.proposals)
    # R rounds ran per dispatch: strictly fewer host boundaries
    assert eng.stats.supersteps < ref_eng.stats.supersteps
    assert eng.stats.rounds_total == eng.stats.supersteps * R


def test_engine_auto_rounds_per_sync(sl_model2, sched_tiny):
    """rounds_per_sync="auto" picks from the power-of-two ladder and still
    serves the exact sample bits."""
    n = 7
    ref = _engine(sl_model2, sched_tiny).serve(_requests(n))
    eng = _engine(sl_model2, sched_tiny, rounds_per_sync="auto")
    out = eng.serve(_requests(n))
    for rid in ref:
        np.testing.assert_array_equal(out[rid], ref[rid])
    # cache keys are (R, budget); auto R draws from the ladder only
    assert {k[0] for k in eng._superstep_fns} <= {1, 2, 4, 8, 16}


def test_engine_rejects_bad_rounds_per_sync(sl_model2, sched_tiny):
    with pytest.raises(ValueError):
        _engine(sl_model2, sched_tiny, rounds_per_sync=0)


def test_superstep_compiles_once_per_R(sl_model2, sched_tiny):
    """One executable per (R, budget): many boundaries, admission waves, and
    window mixes never recompile the superstep program."""
    for kw in (dict(execution="unpacked"),
               dict(execution="packed", round_budget=14,
                    controller=AcceptRateTheta(theta_min=1))):
        eng = _engine(sl_model2, sched_tiny, rounds_per_sync=3, **kw)
        eng.serve(_requests(11))
        eng.serve(_requests(5, seed0=300))
        assert [k[0] for k in eng._superstep_fns] == [3]
        fn = next(iter(eng._superstep_fns.values()))
        assert fn._cache_size() == 1, kw


def test_donation_no_stale_buffers_across_waves(sl_model2, sched_tiny):
    """The superstep donates the slot-state pytree.  After a wave's final
    harvest the engine must dispatch cleanly again on the surviving buffers
    — three back-to-back waves, each bit-identical to a fresh engine.
    Donation is forced on: it defaults off on the CPU backend."""
    eng = _engine(sl_model2, sched_tiny, rounds_per_sync=4, donate=True)
    assert eng._donate
    for wave, (n, seed0) in enumerate([(6, 100), (3, 400), (9, 500)]):
        ref = _engine(sl_model2, sched_tiny).serve(_requests(n, seed0))
        out = eng.serve(_requests(n, seed0))
        assert sorted(out) == sorted(ref), f"wave {wave}"
        for rid in ref:
            np.testing.assert_array_equal(out[rid], ref[rid], err_msg=f"wave {wave}")
    # the engine's own state survived every donation round trip
    assert int(eng.stats.retired) == 18


def test_step_drive_with_supersteps(sl_model2, sched_tiny):
    """The step() drive (open-loop path) counts R rounds per step and
    drains the queue."""
    eng = _engine(sl_model2, sched_tiny, rounds_per_sync=2)
    for r in _requests(6):
        eng.submit(r)
    prev = 0
    while eng.step():
        assert eng.stats.rounds_total == prev + 2
        prev = eng.stats.rounds_total
    assert eng.scheduler.retired == 6
    # timing breakdown accounted every boundary
    assert eng.stats.supersteps * 2 >= eng.stats.rounds_total
    t = eng.stats.timing_breakdown()
    assert t["rounds_per_superstep"] == pytest.approx(2.0)
    assert t["host_sync_s"] >= 0.0 and t["dispatch_s"] > 0.0
