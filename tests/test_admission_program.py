"""One admission program per boundary.

The engines admit a boundary's placed chains in one jitted program per kind:
rows whose start state the program draws from the request key (``key, k0 =
split(key)``, ``y0 = init_y0(k0)``, then ``init_chain_state``), and rows that
carry their own ``y0``.  Every admitted chain state must be bitwise what the
per-request eager recipe gives, on the single engine and on the sharded
fused front end, and no PRNG call may run eagerly on the host per request.
"""

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.serving.worker as worker_mod
from repro.core.asd import init_chain_state
from repro.core.sequential import init_y0
from repro.serving.engine import ContinuousASDEngine, Request
from repro.serving.metrics import EngineStats
from repro.serving.sharded import ShardedASDEngine

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:  # chipbench/ lives at the repo root
    sys.path.insert(0, _ROOT)

THETA = 5
SLOTS = 8
# the fused front end over one shard, and over two where two devices exist
_TWO_DEVICES = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 devices (set XLA_FLAGS="
    "--xla_force_host_platform_device_count)")
ENGINES = ("continuous", "fused", pytest.param("fused2", marks=_TWO_DEVICES))


def _engine(kind, model, sched):
    base = dict(schedule=sched, event_shape=(2,), num_slots=SLOTS,
                theta=THETA, keep_trajectory=True)
    if kind == "continuous":
        return ContinuousASDEngine(lambda cond: model, **base)
    return ShardedASDEngine(lambda cond: model, dispatch="fused",
                            shards=2 if kind == "fused2" else 1, **base)


def _admit(eng):
    """Run one boundary's admission and nothing else."""
    if isinstance(eng, ShardedASDEngine):
        eng._admit_fused()
    else:
        eng._admit_pending()


def _placed(eng):
    """[(request, admitted chain state)] of every occupied slot."""
    if isinstance(eng, ShardedASDEngine):
        return [(w.scheduler.slot_info(s).request, eng.chain_state(i, s))
                for i, w in enumerate(eng.workers)
                for s in w.scheduler.active_slots()]
    return [(eng.scheduler.slot_info(s).request, eng.chain_state(s))
            for s in eng.scheduler.active_slots()]


def _requests(n, mix, seed0=500):
    out = []
    for i in range(n):
        given = mix == "y0" or (mix == "mixed" and i % 2 == 1)
        y0 = (np.asarray(jax.random.normal(
            jax.random.PRNGKey(seed0 + 1000 + i), (2,)), np.float32)
            if given else None)
        out.append(Request(seed0 + i, key=np.asarray(
            jax.random.PRNGKey(seed0 + i)), y0=y0))
    return out


def _eager_state(eng, sched, req):
    """The per-request recipe the engines ran on the host before: draw
    eagerly, then ``init_chain_state``."""
    key = jnp.asarray(req.key)
    if req.y0 is None:
        key, k0 = jax.random.split(key)
        y0 = init_y0(sched, k0, (2,))
    else:
        y0 = jnp.asarray(req.y0, jnp.float32)
    w = eng.workers[0] if isinstance(eng, ShardedASDEngine) else eng
    return init_chain_state(
        sched, y0, key, THETA, w.noise_mode, w.keep_trajectory, w.controller,
        num_branches=w.num_branches, branch_controller=w.branch_controller)


def _assert_states_equal(got, want):
    la, lb = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def warm():
    """One engine of each kind whose admission programs the cases adopt."""
    return {}


def _fresh(kind, warm, sl_model2, sched):
    if kind not in warm:
        warm[kind] = _engine(kind, sl_model2, sched)
    return _engine(kind, sl_model2, sched).adopt_programs(warm[kind])


@pytest.mark.parametrize("kind", ENGINES)
@pytest.mark.parametrize("mix", ["keyed", "y0", "mixed"])
@pytest.mark.parametrize("n", [1, 2, 3, 5])
def test_admitted_states_match_eager_recipe(kind, mix, n, warm, sl_model2,
                                            sched_tiny_ddpm):
    eng = _fresh(kind, warm, sl_model2, sched_tiny_ddpm)
    reqs = _requests(n, mix)
    for r in reqs:
        eng.submit(r)
    _admit(eng)
    placed = _placed(eng)
    assert sorted(r.rid for r, _ in placed) == [r.rid for r in reqs]
    for req, st in placed:
        _assert_states_equal(st, _eager_state(eng, sched_tiny_ddpm, req))
    # one program per kind at its padded width: a mixed boundary compiles
    # (or reuses) both kinds, a single-kind boundary only its own
    widths = (eng._admit_widths if kind == "continuous"
              else eng._fused_admit_widths)
    drawn = sum(r.y0 is None for r in reqs)
    for count, is_drawn in ((drawn, True), (n - drawn, False)):
        if count:
            assert is_drawn in widths[1 << (count - 1).bit_length()]


@pytest.mark.parametrize("kind", ENGINES)
def test_served_samples_match_parent_recipe(kind, warm, sl_model2,
                                            sched_tiny_ddpm):
    """Serving requests that carry a key (or nothing) gives the samples of
    the same requests with their start state drawn on the host first."""
    eng = _fresh(kind, warm, sl_model2, sched_tiny_ddpm)
    worker = eng if kind == "continuous" else eng.workers[0]
    reqs = _requests(5, "mixed", seed0=900)
    if kind != "fused2":  # an unkeyed request's key is its worker's
        reqs += [Request(950), Request(951)]
    predrawn = []
    for r in reqs:
        key = r.key if r.key is not None else worker._request_key(r.rid)
        if r.y0 is None:
            key, k0 = jax.random.split(jnp.asarray(key))
            y0 = np.asarray(init_y0(sched_tiny_ddpm, k0, (2,)))
        else:
            y0 = r.y0
        predrawn.append(Request(r.rid, key=np.asarray(key), y0=y0))
    got = eng.serve(reqs)
    ref = _fresh(kind, warm, sl_model2, sched_tiny_ddpm).serve(predrawn)
    assert sorted(got) == sorted(ref) == sorted(r.rid for r in reqs)
    for rid in ref:
        np.testing.assert_array_equal(got[rid], ref[rid])


class _Counting:
    """Wraps a function and counts its calls on concrete (host) arguments
    apart from those on tracers (inside a program being traced)."""

    def __init__(self, fn):
        self.fn, self.eager, self.traced = fn, 0, 0

    def __call__(self, *args, **kw):
        leaves = jax.tree_util.tree_leaves((args, kw))
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            self.traced += 1
        else:
            self.eager += 1
        return self.fn(*args, **kw)


@pytest.mark.parametrize("kind", ENGINES)
def test_no_eager_prng_call_per_admission(kind, warm, sl_model2,
                                          sched_tiny_ddpm, monkeypatch):
    split = _Counting(jax.random.split)
    draw = _Counting(worker_mod.init_y0)
    monkeypatch.setattr(jax.random, "split", split)
    monkeypatch.setattr(worker_mod, "init_y0", draw)
    # a fresh engine of its own: the width-4 draw program is traced here,
    # through the patched functions
    eng = _engine(kind, sl_model2, sched_tiny_ddpm)
    split.eager = draw.eager = 0
    for r in _requests(4, "keyed", seed0=300):
        eng.submit(r)
    _admit(eng)
    assert draw.traced > 0 and split.traced > 0  # the patch is what runs
    assert (split.eager, draw.eager) == (0, 0)
    # warm: the next boundary at the same width traces nothing either
    split.traced = draw.traced = 0
    for r in _requests(4, "keyed", seed0=400):
        eng.submit(r)
    _admit(eng)
    assert (split.eager, draw.eager, split.traced, draw.traced) == (0,) * 4


@pytest.mark.parametrize("kind", ENGINES)
def test_admit_s_counts_admission_outside_host_s(kind, warm, sl_model2,
                                                 sched_tiny_ddpm):
    eng = _fresh(kind, warm, sl_model2, sched_tiny_ddpm)
    for r in _requests(2, "keyed", seed0=600):
        eng.submit(r)
    eng.step()  # warm the superstep and the width-2 draw program
    attr = "_admit_fn" if kind == "continuous" else "_fused_admit"
    real = getattr(eng, attr)
    pause = 0.2

    def slow(*args):
        time.sleep(pause)
        return real(*args)

    setattr(eng, attr, slow)

    def timers():  # the single engine's stats are live, not a copy
        s = eng.stats
        return s.admit_s, s.dispatch_s + s.fused_dispatch_s + s.host_sync_s

    admit0, host0 = timers()
    for r in _requests(2, "keyed", seed0=700):
        eng.submit(r)
    eng.step()
    admit1, host1 = timers()
    assert admit1 - admit0 >= pause
    assert host1 - host0 < pause
    # merged sums it; the accounted total of timing_breakdown leaves it out
    a = EngineStats(dispatch_s=1.0, device_s=1.0, host_sync_s=1.0,
                    admit_s=5.0)
    b = EngineStats(admit_s=0.25)
    m = EngineStats.merged([a, b])
    assert m.admit_s == 5.25
    t = m.timing_breakdown()
    assert t["admit_s"] == 5.25
    assert t["dispatch_frac"] == pytest.approx(1 / 3)


def test_admit_seconds_on_metrics(warm, sl_model2, sched_tiny_ddpm):
    from repro.serving.obs.registry import MetricsRegistry, instrument_engine

    for kind in ("continuous", "fused"):
        eng = _fresh(kind, warm, sl_model2, sched_tiny_ddpm)
        reg = instrument_engine(MetricsRegistry(), eng)
        for r in _requests(3, "keyed", seed0=800):
            eng.submit(r)
        eng.step()
        samples = reg.snapshot()["asd_admit_seconds"]["samples"]
        assert sum(s["value"] for s in samples) == pytest.approx(
            eng.stats.admit_s)
        assert eng.stats.admit_s > 0.0


def test_program_hlo_lowers_every_admit_width(warm, sl_model2,
                                              sched_tiny_ddpm):
    """The benchmark's analysis tool still lowers ``_admit_fn`` (its
    y0-given variant) at every width the engine has admitted at."""
    from chipbench.attribute import program_hlo

    eng = _fresh("continuous", warm, sl_model2, sched_tiny_ddpm)
    for n, mix in ((1, "keyed"), (3, "y0"), (5, "mixed")):
        for r in _requests(n, mix, seed0=1000 + 10 * n):
            eng.submit(r)
        eng.step()
    assert {1, 2, 4} <= set(eng._admit_widths)
    texts = program_hlo(eng)
    admits = texts[len(eng._superstep_fns):]
    assert len(admits) == len(eng._admit_widths)
    for text in admits:
        assert "HloModule" in text and "asd/admit" in text
