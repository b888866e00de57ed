"""Continuous-batching ASD serving engine: exactness (per-chain output is
bit-identical to the fused single-chain sampler for the same keys), slot
retire/refill under mixed finish times, and metrics accounting.

Compiled programs are shared module-wide: references come from ONE vmapped
asd_sample, and every test engine adopts the warm engine's jitted
superstep/admit programs (same statics => same executables)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import asd_sample
from repro.serving.engine import ContinuousASDEngine, Request
from repro.serving.metrics import EngineStats, RequestMetrics
from repro.serving.scheduler import SlotScheduler

THETA = 5
N_REFS = 13


@pytest.fixture(scope="module")
def refs(sl_model2, sched_tiny, zeros2):
    """Standalone asd_sample results for request keys 100..100+N_REFS."""
    keys = jnp.stack([jax.random.PRNGKey(100 + i) for i in range(N_REFS)])
    fn = jax.jit(jax.vmap(lambda k: asd_sample(
        sl_model2, sched_tiny, zeros2, k, THETA, eager_head=True)))
    return fn(keys)


@pytest.fixture(scope="module")
def warm_engine(sl_model2, sched_tiny):
    eng = ContinuousASDEngine(
        lambda cond: sl_model2, sched_tiny, (2,), num_slots=4, theta=THETA,
        eager_head=True, keep_trajectory=True,
    )
    eng.serve(_requests(2, seed0=10**6))
    return eng


def _engine(warm, sl_model2, sched_tiny, num_slots=4):
    eng = ContinuousASDEngine(
        lambda cond: sl_model2, sched_tiny, (2,), num_slots=num_slots,
        theta=THETA, eager_head=True, keep_trajectory=True,
    )
    if num_slots == warm.num_slots:  # same shapes => reuse compiled programs
        eng.adopt_programs(warm)
    return eng


def _requests(n, seed0=100):
    return [
        Request(i, key=jax.random.PRNGKey(seed0 + i),
                y0=np.zeros((2,), np.float32))
        for i in range(n)
    ]


@pytest.mark.parametrize("rounds_per_sync", [1, 3])
def test_engine_output_matches_asd_sample_bitwise(
    warm_engine, refs, sl_model2, sched_tiny, rounds_per_sync
):
    """More requests than slots: every committed sample equals the
    standalone asd_sample for that request's key, bit for bit — at one
    round per dispatch and with fused supersteps."""
    n = 9
    eng = ContinuousASDEngine(
        lambda cond: sl_model2, sched_tiny, (2,), num_slots=4, theta=THETA,
        eager_head=True, keep_trajectory=True,
        rounds_per_sync=rounds_per_sync,
    ).adopt_programs(warm_engine)
    out = eng.serve(_requests(n))
    assert sorted(out) == list(range(n))
    for i in range(n):
        np.testing.assert_array_equal(out[i], np.asarray(refs.sample[i]))


def test_engine_matches_sequential_law(warm_engine, sl_model2, sched_tiny, zeros2):
    """The committed chains ARE exact DDPM chains (Thm 3): engine moments
    match the sequential sampler's across a moderate batch."""
    from repro.core import sequential_sample

    n = 48
    eng = _engine(warm_engine, sl_model2, sched_tiny)
    out = eng.serve(_requests(n))
    ya = np.stack([out[i] for i in range(n)])
    seq = jax.jit(jax.vmap(
        lambda k: sequential_sample(sl_model2, sched_tiny, zeros2, k)[0]))
    ys = np.asarray(seq(jax.random.split(jax.random.PRNGKey(9), 256)))
    np.testing.assert_allclose(
        ya.mean(0), ys.mean(0), atol=4 * ys.std(0).max() / np.sqrt(n))
    assert ya.std(0).max() < 3 * ys.std(0).max()


def test_slot_retire_and_refill_mixed_finish(warm_engine, sl_model2, sched_tiny):
    """Chains finish at different rounds; freed slots must be refilled and
    every slot reused when requests >> slots."""
    n, slots = 13, 4
    eng = _engine(warm_engine, sl_model2, sched_tiny, num_slots=slots)
    for r in _requests(n):
        eng.submit(r)
    assert eng.scheduler.queue_depth == n
    seen_slots = set()
    while eng.step():
        for s in eng.scheduler.active_slots():
            seen_slots.add(s)
    assert eng.scheduler.retired == n
    assert not eng.scheduler.has_work()
    assert seen_slots == set(range(slots))  # every slot hosted work
    assert len(eng._results) == n
    # mixed finish times: not all chains took the same number of rounds
    per_rounds = {m.rid: m.rounds for m in eng.stats.per_request}
    assert len(set(per_rounds.values())) > 1
    # engine rounds < sum of per-chain rounds (slots overlapped work)
    assert eng.stats.rounds_total < sum(per_rounds.values())


def test_engine_stats_accounting(warm_engine, refs, sl_model2, sched_tiny):
    n = 11
    eng = _engine(warm_engine, sl_model2, sched_tiny)
    out = eng.serve(_requests(n))
    s = eng.stats
    assert len(out) == n
    # requests admitted == retired == scheduler bookkeeping
    assert s.requests == s.retired == n
    assert eng.scheduler.submitted == eng.scheduler.admitted == n
    assert eng.scheduler.retired == n
    # per-chain counters equal the standalone sampler's (exact metrics)
    for m in s.per_request:
        assert m.rounds == int(refs.rounds[m.rid])
        assert m.head_calls == int(refs.head_calls[m.rid])
        assert m.accepts == int(refs.accepts[m.rid])
        assert m.proposals == int(refs.proposals[m.rid])
        assert 0.0 <= m.accept_rate <= 1.0
        assert m.queue_latency >= 0.0 and m.service_time >= 0.0
    assert s.head_calls_total == sum(m.head_calls for m in s.per_request)
    assert s.accepts_total <= s.proposals_total
    assert s.wall_time > 0 and s.throughput() > 0
    summary = s.summary()
    assert summary["requests"] == summary["retired"] == n


def test_rounds_monotone_under_step(warm_engine, sl_model2, sched_tiny):
    eng = _engine(warm_engine, sl_model2, sched_tiny)
    for r in _requests(6):
        eng.submit(r)
    prev = eng.stats.rounds_total
    while eng.step():
        assert eng.stats.rounds_total == prev + 1  # one fused round per step
        prev = eng.stats.rounds_total
        # in-flight + finished never exceeds slot count
        assert len(eng.scheduler.active_slots()) <= eng.num_slots


def test_engine_grs_kernel_matches_core(warm_engine, sl_model2, sched_tiny):
    """grs_impl="kernel" threads the Pallas GRS verifier through the
    continuous engine (interpret-mode off-TPU) and serves samples that match
    the core-verifier engine for the same keys."""
    n = 5
    ref = _engine(warm_engine, sl_model2, sched_tiny).serve(_requests(n))
    eng = ContinuousASDEngine(
        lambda cond: sl_model2, sched_tiny, (2,), num_slots=4, theta=THETA,
        eager_head=True, keep_trajectory=True, grs_impl="kernel",
    )
    out = eng.serve(_requests(n))
    assert sorted(out) == sorted(ref)
    for rid in ref:
        np.testing.assert_allclose(out[rid], ref[rid], atol=1e-5)


def test_scheduler_unit():
    sched = SlotScheduler(2)
    sched.submit("a", now=0.0)
    sched.submit("b", now=1.0)
    sched.submit("c", now=2.0)
    placed = sched.admit(now=3.0, round_idx=0)
    assert [(s, r) for s, r in placed] == [(0, "a"), (1, "b")]
    assert sched.queue_depth == 1 and not sched.free_slots()
    info = sched.retire(0)
    assert info.request == "a" and info.admit_time == 3.0
    with pytest.raises(ValueError):
        sched.retire(0)  # already freed
    placed = sched.admit(now=4.0, round_idx=5)
    assert placed == [(0, "c")]
    assert sched.slot_info(0).admit_round == 5
    assert sched.has_work()
    sched.retire(0)
    sched.retire(1)
    assert not sched.has_work()
    assert sched.submitted == 3 and sched.admitted == 3 and sched.retired == 3


def test_metrics_unit():
    stats = EngineStats()
    stats.requests = 2
    stats.rounds_total = 7
    stats.observe(RequestMetrics(rid=0, queue_latency=0.5, service_time=1.0,
                                 rounds=4, head_calls=2, model_evals=20,
                                 accepts=15, proposals=20))
    stats.observe(RequestMetrics(rid=1, queue_latency=1.5, service_time=2.0,
                                 rounds=6, head_calls=3, model_evals=30,
                                 accepts=10, proposals=25))
    assert stats.retired == 2
    assert stats.accept_rate() == pytest.approx(25 / 45)
    assert stats.mean_queue_latency() == pytest.approx(1.0)
    assert stats.per_request[0].parallel_depth == 6
    assert stats.per_request[0].latency == pytest.approx(1.5)
    stats.wall_time = 4.0
    assert stats.throughput() == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# the double-buffered boundary: step() overlaps when nobody waits for a slot
# ---------------------------------------------------------------------------


def test_step_overlaps_with_free_slots(warm_engine, refs, sl_model2,
                                       sched_tiny):
    """An open-loop drive with free slots: each call leaves its superstep
    in flight and harvests the one before it while it runs; samples are
    still the per-chain asd_sample's, and the counter, the step span and
    /metrics agree on the overlap."""
    from repro.serving.obs import TraceRecorder
    from repro.serving.obs.registry import MetricsRegistry, instrument_engine

    eng = _engine(warm_engine, sl_model2, sched_tiny)
    eng._tracer = tr = TraceRecorder()
    reg = instrument_engine(MetricsRegistry(), eng)
    for r in _requests(2):
        eng.submit(r)
    assert eng.step()
    assert eng._lane.pending is not None  # superstep 1 still in flight
    assert eng.stats.supersteps == 1 and eng.stats.overlapped_supersteps == 0
    assert eng.step()
    assert eng._lane.pending is not None
    assert eng.stats.overlapped_supersteps == 1  # 2 ran while 1 was read
    out = {}
    while eng.step():
        out.update(eng.drain_results())
    out.update(eng.drain_results())
    assert eng._lane.pending is None and not eng.has_work()
    assert sorted(out) == [0, 1]
    for i in out:
        np.testing.assert_array_equal(out[i], np.asarray(refs.sample[i]))
    # every superstep but the first was launched over its predecessor
    s = eng.stats
    assert s.overlapped_supersteps == s.supersteps - 1
    steps = [sp for sp in tr.spans() if sp["name"] == "step"]
    assert [sp["args"]["overlap"] for sp in steps] == (
        [0] + [1] * (s.supersteps - 1) + [0])
    total = reg.snapshot()["asd_overlapped_supersteps_total"]["samples"]
    assert total[0]["value"] == s.overlapped_supersteps


def test_step_harvests_first_when_queue_outgrows_free_slots(
        warm_engine, sl_model2, sched_tiny):
    """With a superstep in flight and more requests queued than free slots,
    the boundary harvests that superstep before it admits, so the slots it
    freed are refilled at once."""
    from repro.serving.obs import TraceRecorder

    eng = _engine(warm_engine, sl_model2, sched_tiny)
    eng._tracer = tr = TraceRecorder()
    for r in _requests(2):
        eng.submit(r)
    eng.step()
    assert eng._lane.pending is not None
    for r in _requests(3, seed0=200):
        eng.submit(Request(10 + r.rid, key=r.key, y0=r.y0))
    assert eng.scheduler.queue_depth > len(eng.scheduler.free_slots())
    eng.step()
    step = [sp for sp in tr.spans() if sp["name"] == "step"][-1]
    inside = [sp for sp in tr.spans() if step["t0"] <= sp["t0"]
              and sp["t0"] + sp["dur"] <= step["t0"] + step["dur"]]
    names = [sp["name"] for sp in sorted(inside, key=lambda sp: sp["t0"])]
    assert names.index("harvest") < names.index("admit")
    assert step["args"]["overlap"] == 0
    while eng.step():
        pass
    assert eng.stats.retired == 5


def test_step_serial_while_requests_queue(warm_engine, sl_model2, sched_tiny):
    """A closed loop with more callers than slots: every call returns with
    nothing in flight, nothing overlaps, and a slot freed by superstep s
    takes its next chain at boundary s+1."""
    eng = _engine(warm_engine, sl_model2, sched_tiny)
    clients, nxt = 8, 8
    for r in _requests(clients):
        eng.submit(r)
    freed_at = {}  # slot -> engine rounds when its chain retired
    refills = 0
    for _ in range(12):
        before = {s: eng.scheduler.slot_info(s).request.rid
                  for s in eng.scheduler.active_slots()}
        for slot, rounds in list(freed_at.items()):
            assert eng.scheduler.slot_info(slot) is None
        eng.step()
        assert eng._lane.pending is None
        for slot, rounds in list(freed_at.items()):
            # refilled by this boundary's admission, before its launch
            info = eng.scheduler.slot_info(slot)
            assert info is not None and info.admit_round == rounds
            refills += 1
            del freed_at[slot]
        done = eng.drain_results()
        for slot, rid in before.items():
            if rid in done:
                freed_at[slot] = eng.stats.rounds_total
        for rid in range(nxt, nxt + len(done)):  # each caller sends again
            eng.submit(_requests(1, seed0=100 + rid)[0])
        nxt += len(done)
    assert refills > 0
    assert eng.stats.overlapped_supersteps == 0


def test_retired_chain_drained_by_the_next_launch(warm_engine, sl_model2,
                                                  sched_tiny):
    """A chain whose last step commits in superstep s is in drain_results()
    when the call that launched s+1 returns, and not before."""
    eng = _engine(warm_engine, sl_model2, sched_tiny)
    eng.submit(_requests(1)[0])
    finished_in = None
    while eng.step():
        launched = eng.stats.supersteps
        done = eng.drain_results()
        if finished_in is not None:
            assert launched == finished_in + 1 and 0 in done
            break
        assert not done
        if int(eng.chain_state(0).a) >= sched_tiny.K:
            finished_in = launched
    assert finished_in is not None
    while eng.step():
        pass
    assert not eng.has_work()


def test_round_time_starts_at_predecessor_completion(warm_engine, sl_model2,
                                                     sched_tiny):
    """A superstep launched behind its predecessor is timed from the later
    of its launch and the predecessor's completion, so the seconds-per-round
    estimate leaves out the wait behind the predecessor."""
    import time

    eng = _engine(warm_engine, sl_model2, sched_tiny)
    eng.submit(_requests(1)[0])
    eng.step()
    sync, snap, R, t_dispatch, cold = eng._lane.pending
    assert not cold
    # as if it had been launched 10 s before its predecessor completed
    eng._lane.pending = (sync, snap, R, t_dispatch - 10.0, cold)
    eng._done_at = time.perf_counter()
    eng._lane.flush()
    assert 0.0 < eng._spr_ewma < 1.0


@pytest.mark.parametrize("traffic", ["free_slots", "queued"])
@pytest.mark.parametrize("kind", ["continuous", "sharded"])
def test_chain_state_reads_one_superstep_apart(warm_engine, sl_model2,
                                               sched_tiny, kind, traffic):
    """The benchmark's check reads a slot's state after step(), steps once
    and reads it again: the two reads are exactly one superstep of the
    engine's own program apart, whether the boundary overlapped or not."""
    from repro.serving.sharded import ShardedASDEngine

    kw = dict(schedule=sched_tiny, event_shape=(2,), num_slots=4,
              theta=THETA, eager_head=True, keep_trajectory=True)
    if kind == "continuous":
        eng = ContinuousASDEngine(lambda cond: sl_model2, **kw)
        worker, read = eng, eng.chain_state
    else:
        eng = ShardedASDEngine(lambda cond: sl_model2, shards=1, **kw)
        worker = eng.workers[0]
        read = lambda slot: eng.chain_state(0, slot)  # noqa: E731
    eng.adopt_programs(warm_engine)
    n = 2 if traffic == "free_slots" else 7
    for r in _requests(n):
        eng.submit(r)
    eng.step()
    fn = worker._get_superstep(1, None)
    pairs = {True: 0, False: 0}  # by whether the read followed an overlap
    while eng.has_work():
        occ = worker.scheduler.active_slots()
        pre = {s: read(s) for s in occ}
        batch = jax.tree_util.tree_map(np.array, worker._states)
        replay, _ = fn(batch, worker._conds, worker._params,
                       worker._weights_dev)
        was = worker.stats.overlapped_supersteps
        eng.step()
        # in flight exactly when the call launched and nobody is queued
        assert (worker._lane.pending is not None) == (
            eng.has_work() and worker.scheduler.queue_depth == 0)
        overlapped = worker.stats.overlapped_supersteps > was
        for s, st in pre.items():
            if int(st.a) >= sched_tiny.K:
                continue
            pairs[overlapped] += 1
            post = read(s)
            want = jax.tree_util.tree_map(lambda x: x[s], replay)
            for a, b in zip(jax.tree_util.tree_leaves(post),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pairs[True] >= 2
    assert (pairs[False] >= 2) == (traffic == "queued")
