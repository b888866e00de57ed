"""Serving observability: trace ring/export, metrics registry/endpoints,
health + drain signals, and the tracing-changes-nothing guarantees."""

import glob
import json
import logging
import re
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from repro.core import StaticTheta
from repro.serving.engine import ContinuousASDEngine, Request
from repro.serving.metrics import EngineStats
from repro.serving.obs import (
    MetricsRegistry,
    MetricsServer,
    PROM_CONTENT_TYPE,
    TraceRecorder,
    instrument_engine,
    span,
)
from repro.serving.sharded import ShardedASDEngine

THETA = 5


def _engine(sl_model2, sched_tiny, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("theta", THETA)
    kw.setdefault("controller", StaticTheta())
    return ContinuousASDEngine(
        lambda cond: sl_model2, sched_tiny, (2,),
        eager_head=True, keep_trajectory=False, **kw)


def _requests(n, seed0=0):
    return [Request(i, key=jax.random.PRNGKey(seed0 + i),
                    y0=np.zeros((2,), np.float32)) for i in range(n)]


# ---------------------------------------------------------------------------
# TraceRecorder
# ---------------------------------------------------------------------------


class TestTraceRecorder:
    def test_ring_drops_oldest(self):
        tr = TraceRecorder(capacity=4)
        for i in range(7):
            tr.add_span(f"s{i}", float(i), float(i) + 0.5)
        assert len(tr) == 4
        assert tr.dropped == 3
        assert [s["name"] for s in tr.spans()] == ["s3", "s4", "s5", "s6"]

    def test_disabled_records_nothing(self):
        tr = TraceRecorder(capacity=8, enabled=False)
        tr.add_span("x", 0.0, 1.0)
        tr.add_instant("y", 0.5)
        assert len(tr) == 0
        assert tr.to_chrome()["traceEvents"] == []

    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            TraceRecorder(capacity=0)

    def test_chrome_export_schema_and_determinism(self, tmp_path):
        tr = TraceRecorder(capacity=16)
        t0 = tr.epoch
        tr.add_span("dispatch", t0 + 0.001, t0 + 0.002, pid=0, tid=4,
                    pname="shard-0", tname="dispatch", args={"R": 2})
        tr.add_span("request", t0 + 0.001, t0 + 0.005, pid=0, tid=1,
                    tname="slot-1", args={"rid": 7})
        tr.add_instant("route", t0 + 0.0005, pid=1, tid=2, pname="frontend")
        doc = tr.to_chrome()
        evs = doc["traceEvents"]
        metas = [e for e in evs if e["ph"] == "M"]
        spans = [e for e in evs if e["ph"] == "X"]
        instants = [e for e in evs if e["ph"] == "i"]
        assert {m["args"]["name"] for m in metas} == {
            "shard-0", "frontend", "dispatch", "slot-1"}
        assert len(spans) == 2 and len(instants) == 1
        for e in spans:
            assert e["ts"] >= 0 and e["dur"] > 0  # microseconds, rel epoch
        assert instants[0]["s"] == "t"
        assert doc["droppedEvents"] == 0
        # records sort by timestamp: the route instant leads
        assert [e["name"] for e in evs if e["ph"] != "M"][0] == "route"

        p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
        tr.export_chrome_trace(str(p1))
        tr.export_chrome_trace(str(p2))
        assert p1.read_bytes() == p2.read_bytes()  # export is deterministic
        assert json.loads(p1.read_text())["displayTimeUnit"] == "ms"

    def test_clear_keeps_names(self):
        tr = TraceRecorder(capacity=4)
        tr.add_span("a", 0.0, 1.0, pid=0, pname="shard-0")
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0
        names = [e["args"]["name"] for e in tr.to_chrome()["traceEvents"]
                 if e["ph"] == "M"]
        assert names == ["shard-0"]


# ---------------------------------------------------------------------------
# Traced engines: spans appear, bits do not move
# ---------------------------------------------------------------------------


class TestEngineTracing:
    def test_spans_and_bit_parity(self, sl_model2, sched_tiny, tmp_path):
        plain = _engine(sl_model2, sched_tiny)
        out_plain = plain.serve(_requests(9))

        tr = TraceRecorder()
        traced = _engine(sl_model2, sched_tiny, tracer=tr)
        traced.adopt_programs(plain)
        out_traced = traced.serve(_requests(9))

        assert out_plain.keys() == out_traced.keys()
        for rid in out_plain:  # tracing is host bookkeeping: bits identical
            np.testing.assert_array_equal(out_plain[rid], out_traced[rid])

        names = {s["name"] for s in tr.spans()}
        assert {"dispatch", "device_wait", "harvest",
                "queued", "request"} <= names
        req_spans = [s for s in tr.spans() if s["name"] == "request"]
        assert len(req_spans) == 9
        assert {s["args"]["rid"] for s in req_spans} == set(range(9))
        assert all(s["tid"] < traced.num_slots for s in req_spans)
        bound = [s for s in tr.spans() if s["name"] == "dispatch"]
        assert all(s["tid"] == traced.num_slots for s in bound)

        doc = tr.export_chrome_trace(str(tmp_path / "t.json"))
        assert doc["droppedEvents"] == 0
        assert json.loads((tmp_path / "t.json").read_text())["traceEvents"]

    def test_tracing_overhead_bounded(self, sl_model2, sched_tiny):
        # the acceptance bar is 3% on a quiet box; CI boxes are not quiet,
        # so the automated bound is deliberately lenient — it catches a
        # tracer that serializes the loop, not percent-level jitter
        import time

        plain = _engine(sl_model2, sched_tiny)
        plain.serve(_requests(8))  # compile
        walls = {}
        for name, tr in (("off", None), ("on", TraceRecorder())):
            eng = _engine(sl_model2, sched_tiny, tracer=tr)
            eng.adopt_programs(plain)
            t0 = time.perf_counter()
            eng.serve(_requests(16, seed0=100))
            walls[name] = time.perf_counter() - t0
        assert walls["on"] < 3.0 * walls["off"]

    @pytest.mark.skipif(len(jax.devices()) < 2,
                        reason="needs >= 2 devices (set XLA_FLAGS="
                               "--xla_force_host_platform_device_count)")
    def test_sharded_route_instants_and_frontend_lane(
            self, sl_model2, sched_tiny):
        tr = TraceRecorder()
        eng = ShardedASDEngine(
            lambda cond: sl_model2, sched_tiny, (2,), num_slots=4, shards=2,
            theta=THETA, eager_head=True, keep_trajectory=False,
            dispatch="fused", controller=StaticTheta(), tracer=tr)
        eng.serve(_requests(8))
        routes = [s for s in tr.spans() if s["name"] == "route"]
        assert len(routes) == 8
        assert all(s["pid"] == eng.num_shards for s in routes)
        fused = [s for s in tr.spans() if s["name"] == "fused_dispatch"]
        assert fused and all(s["pid"] == eng.num_shards for s in fused)


# ---------------------------------------------------------------------------
# Program spans and scopes in a profiler trace
# ---------------------------------------------------------------------------


def _profiled(tmp_path, fn):
    """Run ``fn`` under the profiler; returns the trace's ProfileData."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    return ProfileData.from_file(path)


def _host_events(pd, prefix="repro."):
    """(name cut at '#', start_ns, end_ns) of the host events named
    ``prefix...``, sorted by start."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name.split("#")[0], e.start_ns, e.end_ns)
                           for e in line.events
                           if e.name.startswith(prefix))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _children(events, parent):
    _, s, e = parent
    return [c for c in events
            if c is not parent and s <= c[1] and c[2] <= e]


class TestProgramSpans:
    def test_step_encloses_its_boundary_in_order(self, sl_model2, sched_tiny,
                                                 tmp_path):
        eng = _engine(sl_model2, sched_tiny)
        for r in _requests(6):
            eng.submit(r)

        def drive():
            for _ in range(4):
                eng.step()

        events = _host_events(_profiled(tmp_path, drive))
        steps = [e for e in events if e[0] == "repro.step"]
        assert len(steps) == 4
        for i, st in enumerate(steps):
            kids = _children(events, st)
            # direct children: drop the compile nested inside admit
            admit = [k for k in kids if k[0] == "repro.admit"]
            assert len(admit) == 1
            top = [k for k in kids if not any(
                o is not k and o[1] <= k[1] and k[2] <= o[2] for o in kids)]
            names = [k[0] for k in top]
            launch = "repro.compile" if i == 0 else "repro.dispatch"
            assert names == ["repro.admit", launch, "repro.device_wait",
                             "repro.harvest"], names
        # the cold first superstep compiles its program and the admission
        # scatter's; later ones are warm
        first = [k[0] for k in _children(events, steps[0])]
        assert first.count("repro.compile") == 2
        assert all("repro.compile" not in [k[0] for k in
                                           _children(events, st)]
                   for st in steps[1:])

    def test_ring_and_profiler_share_a_clock(self, tmp_path):
        tr = TraceRecorder()
        rec = []

        def once():
            with span(tr, "probe", pid=0, tid=0):
                rec.append(0)

        pd = _profiled(tmp_path, once)
        env = {k: v for p in pd.planes if p.name == "Task Environment"
               for k, v in p.stats}
        (name, start_ns, _), = _host_events(pd, "repro.probe")
        ring, = [s for s in tr.spans() if s["name"] == "probe"]
        trace_t0 = (env["profile_start_time"] + start_ns) * 1e-9
        assert abs(trace_t0 - ring["t0"]) < 100e-6

    def test_span_off_records_nothing(self):
        tr = TraceRecorder(enabled=False)
        with span(tr, "x", a=1) as sp:
            sp.set(b=2)
        with span(None, "y"):
            pass
        assert len(tr) == 0

    def test_samples_identical_with_ring_and_profiler(
            self, sl_model2, sched_tiny, tmp_path):
        plain = _engine(sl_model2, sched_tiny)
        golden = plain.serve(_requests(9))
        ring = _engine(sl_model2, sched_tiny, tracer=TraceRecorder())
        ring.adopt_programs(plain)
        profiled = _engine(sl_model2, sched_tiny, tracer=TraceRecorder())
        profiled.adopt_programs(plain)
        got = {"ring": ring.serve(_requests(9))}
        _profiled(tmp_path, lambda: got.update(
            profiled=profiled.serve(_requests(9))))
        for out in got.values():
            assert out.keys() == golden.keys()
            for rid in golden:
                np.testing.assert_array_equal(out[rid], golden[rid])


def _superstep_hlo(sl_model2, sched_tiny, **lane):
    eng = _engine(sl_model2, sched_tiny, **lane)
    R, B = eng._pick_rounds(), eng._pick_budget()
    fn = eng._get_superstep(R, B)
    extra = [np.int32(B)] if eng._budget_as_data else []
    return fn.lower(eng._states, eng._conds, eng._params, eng._weights_dev,
                    *extra).compile().as_text()


@pytest.mark.parametrize("lane", [
    dict(execution="unpacked", grs_impl="core"),
    dict(execution="packed", round_impl="fused", pack_impl="kernel",
         grs_impl="kernel", rounds_per_sync=2),
], ids=["unpacked-core", "packed-fused-kernel"])
def test_superstep_ops_carry_round_scopes(sl_model2, sched_tiny, lane):
    hlo = _superstep_hlo(sl_model2, sched_tiny, **lane)
    op_names = " ".join(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("asd/propose", "asd/verify", "asd/grs", "asd/commit",
                  "asd/sync"):
        assert scope in op_names, scope
    if lane["execution"] == "packed":
        assert "asd/pack" in op_names
        # the kernels carry their stable names
        assert "fused_gather" in hlo and "fused_verify_commit" in hlo


# ---------------------------------------------------------------------------
# MetricsRegistry / Prometheus exposition
# ---------------------------------------------------------------------------


_SAMPLE_RE = (r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
              r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
              r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? -?[0-9].*$')


class TestMetricsRegistry:
    def test_prometheus_text_parses(self):
        import re

        reg = MetricsRegistry()
        c = reg.counter("asd_requests_total", "requests", shard="0")
        c.inc(3)
        reg.gauge("asd_accept_rate", "rate", shard="0").set(0.75)
        h = reg.histogram("asd_latency_seconds", "latency",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = reg.render()
        helps = [l for l in text.splitlines() if l.startswith("# HELP")]
        types = [l for l in text.splitlines() if l.startswith("# TYPE")]
        assert len(helps) == 3 and len(types) == 3
        for line in text.splitlines():
            if line.startswith("#") or not line:
                continue
            assert re.match(_SAMPLE_RE, line), line
        assert 'asd_requests_total{shard="0"} 3' in text
        # histogram buckets are cumulative and capped by +Inf == _count
        assert 'asd_latency_seconds_bucket{le="0.1"} 1' in text
        assert 'asd_latency_seconds_bucket{le="1"} 2' in text
        assert 'asd_latency_seconds_bucket{le="+Inf"} 3' in text
        assert "asd_latency_seconds_count 3" in text

    def test_counter_rejects_negative_and_kind_conflicts(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total")
        with pytest.raises(ValueError):
            c.inc(-1)
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_get_or_create_returns_same_child(self):
        reg = MetricsRegistry()
        assert reg.counter("y_total", shard="1") is reg.counter(
            "y_total", shard="1")

    def test_callback_gauge_reads_at_scrape(self):
        reg = MetricsRegistry()
        box = {"v": 1.0}
        reg.gauge("live", "callback", fn=lambda: box["v"])
        assert "live 1" in reg.render()
        box["v"] = 2.5
        assert "live 2.5" in reg.render()

    def test_snapshot_round_trips_json(self):
        reg = MetricsRegistry()
        reg.counter("z_total", shard="0").inc(2)
        snap = json.loads(json.dumps(reg.snapshot()))
        assert snap["z_total"]["type"] == "counter"
        assert snap["z_total"]["samples"][0]["value"] == 2

    def test_instrument_engine(self, sl_model2, sched_tiny):
        eng = _engine(sl_model2, sched_tiny)
        eng.serve(_requests(6))
        reg = MetricsRegistry()
        instrument_engine(reg, eng)
        text = reg.render()
        assert 'asd_requests_total{shard="0"} 6' in text
        assert 'asd_retired_total{shard="0"} 6' in text
        assert "asd_accept_rate" in text
        assert "asd_queue_depth_peak" in text
        assert 'asd_completion_latency_seconds{quantile="p99"' in text
        snap = reg.snapshot()
        assert snap["asd_supersteps_total"]["samples"][0]["value"] > 0


# ---------------------------------------------------------------------------
# HTTP endpoints
# ---------------------------------------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=5) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


class TestMetricsServer:
    def test_endpoints(self):
        reg = MetricsRegistry()
        reg.counter("up_total").inc()
        health = {"status": "ok", "shards": []}
        srv = MetricsServer(reg, health_fn=lambda: health, port=0)
        srv.start()
        try:
            code, ctype, body = _get(srv.url + "/metrics")
            assert code == 200 and ctype == PROM_CONTENT_TYPE
            assert "up_total 1" in body
            code, _, body = _get(srv.url + "/metrics.json")
            assert code == 200 and json.loads(body)["up_total"]
            code, _, body = _get(srv.url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + "/nope")
            assert ei.value.code == 404
            # unhealthy flips /healthz to 503, payload preserved
            health["status"] = "backpressure"
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + "/healthz")
            assert ei.value.code == 503
            assert json.loads(ei.value.read())["status"] == "backpressure"
        finally:
            srv.stop()

    def test_healthz_reflects_engine_saturation(self, sl_model2, sched_tiny):
        eng = _engine(sl_model2, sched_tiny, num_slots=2)
        reg = MetricsRegistry()
        instrument_engine(reg, eng)
        srv = MetricsServer(reg, health_fn=eng.healthz, port=0)
        srv.start()
        try:
            code, _, body = _get(srv.url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"
            # queue more than a slot batch without stepping: backpressure
            for r in _requests(6):
                eng.submit(r)
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(srv.url + "/healthz")
            assert ei.value.code == 503
            doc = json.loads(ei.value.read())
            assert doc["status"] == "backpressure"
            assert doc["shards"][0]["queue_depth"] == 6
            eng.serve([])  # drain the queue -> healthy again
            code, _, body = _get(srv.url + "/healthz")
            assert code == 200 and json.loads(body)["status"] == "ok"
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Health / drain semantics on the engines
# ---------------------------------------------------------------------------


class TestHealthAndDrain:
    def test_drain_gate_rejects_submissions(self, sl_model2, sched_tiny):
        eng = _engine(sl_model2, sched_tiny)
        eng.begin_drain()
        assert eng.healthz()["status"] == "draining"
        with pytest.raises(RuntimeError, match="draining"):
            eng.submit(_requests(1)[0])

    def test_sharded_healthz_worst_status_wins(self, sl_model2, sched_tiny):
        eng = ShardedASDEngine(
            lambda cond: sl_model2, sched_tiny, (2,), num_slots=4, shards=2,
            theta=THETA, eager_head=True, keep_trajectory=False,
            controller=StaticTheta())
        assert eng.healthz()["status"] == "ok"
        assert len(eng.health()) == 2
        eng.workers[1].begin_drain()
        assert eng.healthz()["status"] == "draining"
        with pytest.raises(RuntimeError, match="draining"):
            eng.submit(_requests(1)[0])

    def test_queue_watermark(self, sl_model2, sched_tiny):
        eng = _engine(sl_model2, sched_tiny, num_slots=2)
        eng.serve(_requests(7))
        s = eng.stats
        assert s.queue_depth == 0  # drained
        assert s.queue_depth_peak >= 5  # 7 submitted over 2 slots
        assert 0.0 <= s.slot_occupancy <= 1.0

    def test_stats_health_merge_rules(self):
        a = EngineStats(queue_depth=2, queue_depth_peak=5,
                        slot_occupancy=1.0, admission_pressure=0.5,
                        draining=False)
        b = EngineStats(queue_depth=1, queue_depth_peak=9,
                        slot_occupancy=0.5, admission_pressure=0.75,
                        draining=True)
        m = EngineStats.merged([a, b])
        assert m.queue_depth == 3  # sums: total queued behind the fleet
        assert m.queue_depth_peak == 9  # max: the worst shard's watermark
        assert m.slot_occupancy == pytest.approx(0.75)  # mean
        assert m.admission_pressure == pytest.approx(0.75)  # max
        assert m.draining is True  # any
        assert "health" in m.summary()

    def test_fused_dispatch_attributed_to_frontend(
            self, sl_model2, sched_tiny):
        eng = ShardedASDEngine(
            lambda cond: sl_model2, sched_tiny, (2,), num_slots=4, shards=1,
            theta=THETA, eager_head=True, keep_trajectory=False,
            dispatch="fused", controller=StaticTheta())
        eng.serve(_requests(8))
        m = eng.stats
        # the fused front-end launch is ONE wall, not a per-worker split
        assert m.fused_dispatch_s > 0.0
        assert eng.workers[0].stats.dispatch_s == 0.0
        t = m.timing_breakdown()
        assert t["fused_dispatch_s"] == pytest.approx(m.fused_dispatch_s)
        assert 0.0 <= t["fused_dispatch_frac"] <= 1.0


# ---------------------------------------------------------------------------
# Logging hierarchy
# ---------------------------------------------------------------------------


class TestServingLogs:
    def test_serve_lifecycle_logged(self, sl_model2, sched_tiny, caplog):
        eng = _engine(sl_model2, sched_tiny)
        with caplog.at_level(logging.INFO, logger="repro.serving"):
            eng.serve(_requests(5))
        drained = [r for r in caplog.records
                   if "serve drained" in r.getMessage()]
        assert drained and drained[0].name == "repro.serving.engine"

    def test_admission_deferral_counted_and_logged(self, caplog):
        from repro.serving.scheduler import (
            AdmissionContext, SlotScheduler, make_policy)

        sched = SlotScheduler(num_slots=2, policy=make_policy("budget"))
        sched.submit(Request(0, key=jax.random.PRNGKey(0)), 0.0)
        # live demand at 2x the budget: the policy must defer, not drop
        ctx = AdmissionContext(theta_max=4, round_budget=8, live_demand=16)
        with caplog.at_level(logging.DEBUG, logger="repro.serving"):
            assert sched.admit(0.0, 0, ctx) == []
        assert sched.deferred == 1
        assert sched.queue_depth == 1  # deferred stays queued
        assert any("admission deferred" in r.getMessage()
                   for r in caplog.records)

    def test_drain_logged(self, sl_model2, sched_tiny, caplog):
        eng = _engine(sl_model2, sched_tiny)
        with caplog.at_level(logging.INFO, logger="repro.serving"):
            eng.begin_drain()
        assert any("draining" in r.getMessage() for r in caplog.records)
