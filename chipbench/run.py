#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 chipbench/run.py --workload ldm-dit.batch --seed 7 --seconds 30 --trace 0

A cell is one ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``chipbench/configs/<config>.json``) under a traffic mix
(``chipbench/traffic/<mix>.json``).  The run builds the serving engine
through the program's own entry points (``repro.launch.serve``), makes the
configuration's weights and, from ``--seed``, the request keys and arrival
phases on the device, warms up every
shape the cell's traffic uses, drives ``Engine.step`` for ``--seconds``
seconds between two superstep boundaries, then checks what the timed
program produced against the plain reference (``chipbench/check.py``).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and they are its
per-layer metrics, each read by ``chipbench/metrics/<name>.py``.  The last
line of standard output is one JSON object; the numbers compared for
``correct`` close it under ``checks`` and are the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench_trace"
TRACE_S = 10.0  # the longest window a --trace 1 run measures
WARM_RID = 1 << 29  # rids of warm-up requests
POOL_SEED = 0  # the seed of the fixed pool of noise keys
CHECK_RID = 1 << 30  # rids of the requests the check starts


class NoChip(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ----------------------------------------------------------------- the cell


def load_cell(name: str) -> dict:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    from chipbench import traffic

    return {
        "bench": bench,
        "workload": w,
        "cfg": json.loads((ROOT / conf["file"]).read_text()),
        "traffic": traffic.load(w["traffic"]),
    }


def metric_names(bench: dict, cell: str, trace: bool) -> list:
    key = "per_layer" if trace else "end_to_end"
    return [m["name"] for m in bench[key]
            if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, else, for a
    name split by cell kind (``mfu.batch``), the reader of the quantity
    (``metrics/mfu.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{name.split('.')[0]}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ set-up


def configure_jax():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def require_chips(jax, chips: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


def serve_argv(cfg: dict, tr: dict) -> list:
    lane = cfg["lane"]
    return [
        "--model", cfg["registry_model"], "--K", str(cfg["K"]),
        "--theta", str(cfg["theta"]), "--slots", str(tr["slots"]),
        "--chains", str(tr["slots"]), "--mesh", "1x1",
        "--execution", lane["execution"], "--round-impl", lane["round_impl"],
        "--pack-impl", lane["pack_impl"], "--grs-impl", lane["grs_impl"],
        "--rounds-per-sync", str(lane["rounds_per_sync"]),
    ]


def denoiser_config(cfg: dict, fam):
    """The registry's ``registry_model`` with each key of the configuration's
    model section written onto the field of that name, of its backbone or
    of the ``DenoiserConfig``; the program builds whatever sizes its config
    object holds.  Structured fields, such as the layer pattern, stay the
    registry entry's."""
    from dataclasses import fields, replace

    from repro.configs.registry import get_denoiser_config

    m = cfg["model"]
    dc = get_denoiser_config(cfg["registry_model"])
    outer = {f.name for f in fields(dc)} - {"backbone"}
    inner = {f.name for f in fields(dc.backbone)}
    unknown = sorted(set(m) - outer - inner)
    if unknown:
        raise SystemExit(f"{cfg['name']}.json: model keys {unknown} name no "
                         f"field of {cfg['registry_model']}'s config")
    backbone = replace(dc.backbone, **{k: v for k, v in m.items()
                                       if k in inner})
    dc = replace(dc, backbone=backbone,
                 **{k: v for k, v in m.items() if k in outer})
    check_model_matches(dc, cfg, fam)
    return dc


def check_model_matches(dc, cfg: dict, fam) -> None:
    """The program's model is the one the configuration file describes:
    every key of its model section reads back, and what the family's
    reference assumes beyond them holds."""
    m = cfg["model"]
    got = {k: getattr(dc.backbone if hasattr(dc.backbone, k) else dc, k)
           for k in m}
    bad = {k: (v, m[k]) for k, v in got.items() if v != m[k]}
    departs = fam.program_departs(dc)
    if bad or departs:
        raise SystemExit(f"{cfg['registry_model']} differs from "
                         f"{cfg['name']}.json: {bad} {departs}")


def build(cell: dict, devices: list):
    """The engine the cell describes, over the configuration's weights."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from chipbench.model import family, make_weights
    from repro.launch.serve import build_engine, build_parser, make_mesh
    from repro.models.diffusion import denoiser_init
    from repro.nn.param import unbox

    cfg, tr = cell["cfg"], cell["traffic"]
    fam = family(cfg)
    args = build_parser().parse_args(serve_argv(cfg, tr))
    dc = denoiser_config(cfg, fam)
    mesh = make_mesh(args.mesh)
    params = make_weights(cfg, NamedSharding(mesh, P()))
    want = jax.eval_shape(lambda k: unbox(denoiser_init(k, dc)),
                          jax.random.PRNGKey(0))
    shapes = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: (tuple(x.shape), str(x.dtype)), t)
    if shapes(params) != shapes(want):
        raise SystemExit("the weight recipe does not match the program's "
                         "parameter tree")
    eng, layout = build_engine(args, mesh, dc, params)
    return eng, layout, params


# ------------------------------------------------------------- the engine


class Harness:
    """The harness's view of one engine: submit, step and read chain state
    through the engine's public calls, each under a trace annotation."""

    def __init__(self, eng, cfg: dict, tr: dict, seed: int):
        import jax
        import numpy as np

        from chipbench.model import request_base

        self.eng, self.tr = eng, tr
        self.K = int(cfg["K"])
        self.annotate = jax.profiler.TraceAnnotation
        self._np, self._jax = np, jax
        # the seeds are arguments, not constants: one program for every
        # seed, found in the compilation cache after a checkout's first run
        self._fold = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))
        self._seed_key = request_base(seed)
        self._pool_key = request_base(POOL_SEED)
        self._width = 1 << (int(tr["clients"]) - 1).bit_length()
        self.pool = int(tr.get("pooled_keys", 0))
        self.perm = np.random.default_rng(
            [seed & 0xFFFFFFFF, seed >> 32, 13]).permutation(self.pool)
        self.completed = {}  # rid -> host time its result was drained

    def keys(self, rids) -> "np.ndarray":
        """Noise keys of requests ``rids``: the first ``pooled_keys`` rids
        take the keys of a fixed pool in an order drawn from the seed, so
        that every seed gives them the same work; the rest are drawn from
        the seed."""
        np = self._np
        rids = np.asarray(rids, np.int64)
        keys = self._folded(self._seed_key, rids)
        pooled = rids < self.pool
        if pooled.any():
            keys[pooled] = self._folded(self._pool_key, self.perm[rids[pooled]])
        return keys

    def _folded(self, base, ids) -> "np.ndarray":
        """``base`` folded with each of ``ids``, the batch padded to the
        traffic's number of clients (or the next power of two past a larger
        batch) and the padding dropped: the warm-up's first batch compiles
        the one program that every batch of the window runs."""
        np = self._np
        n = len(ids)
        buf = np.zeros(max(self._width, 1 << max(n - 1, 0).bit_length()),
                       np.int32)
        buf[:n] = ids
        return np.array(self._fold(base, buf))[:n]

    def compile_admission(self, n: int) -> None:
        """Compile the engine's program that admits ``n`` keyed requests at
        a boundary, which the engine would otherwise compile when it first
        admits ``n``.  The engine has no public call for this; one without
        ``_admit_fn`` compiles the width there."""
        fn = getattr(self.eng, "_admit_fn", None)
        if fn is None:
            log(f"no admission program to compile for {n} requests")
            return
        sds = self._jax.ShapeDtypeStruct
        fn.lower(self.eng._states, None, sds((n, 2), self._np.uint32),
                 sds((n,), self._np.int32)).compile()

    def submit(self, rids, keys=None) -> None:
        from repro.serving.engine import Request

        keys = self.keys(rids) if keys is None else keys
        with self.annotate("chipbench.submit"):
            for rid, key in zip(rids, keys):
                self.eng.submit(Request(int(rid), key=key))

    def step(self) -> bool:
        with self.annotate("chipbench.step"):
            more = self.eng.step()
        now = time.perf_counter()
        for rid in self.eng.drain_results():
            self.completed[rid] = now
        return more

    def has_work(self) -> bool:
        return self.eng.has_work()

    def chain(self, slot: int, full: bool = False) -> dict:
        st = self.eng.chain_state(slot)
        names = ["a", "rounds", "model_evals", "accepts"]
        if full:
            names += ["k_u", "k_xi", "v_valid", "v_cache"]
        with self.annotate("chipbench.read_state"):
            got = self._jax.device_get(
                {n: getattr(st, n) for n in names} | (
                    {"y": st.y[0]} if full else {}))
        return got

    def occupants(self) -> dict:
        """{rid: slot} of every chain holding a slot."""
        sch = self.eng.scheduler
        return {sch.slot_info(slot).request.rid: slot
                for slot in sch.active_slots()}

    def snapshot(self) -> dict:
        """Counters of every chain in a slot, the retired requests so far,
        and the engine's host-time counters."""
        s = self.eng.stats
        chains = {rid: self.chain(slot)
                  for rid, slot in self.occupants().items()}
        return {
            "chains": chains,
            "retired": {m.rid: m for m in s.per_request},
            "host_s": s.dispatch_s + s.fused_dispatch_s + s.host_sync_s,
            "dropped": s.dropped,
        }


def window_work(s0: dict, s1: dict, K: int) -> dict:
    """Steps committed, rounds and model evaluations of every chain between
    two snapshots: a chain counts its counters at the end (or at
    retirement) less those at the start (0 if admitted in between)."""
    zero = {"a": 0, "rounds": 0, "model_evals": 0}
    steps = rounds = evals = chains = 0
    new_retired = {r: m for r, m in s1["retired"].items()
                   if r not in s0["retired"]}
    for rid in set(s0["chains"]) | set(s1["chains"]) | set(new_retired):
        start = s0["chains"].get(rid, zero)
        if rid in s1["chains"]:
            end = s1["chains"][rid]
        elif rid in new_retired:
            m = new_retired[rid]
            end = {"a": K, "rounds": m.rounds, "model_evals": m.model_evals}
        else:
            continue
        chains += 1
        steps += int(end["a"]) - int(start["a"])
        rounds += int(end["rounds"]) - int(start["rounds"])
        evals += int(end["model_evals"]) - int(start["model_evals"])
    return {"steps": steps, "rounds": rounds, "evals": evals,
            "chains": chains, "retired": len(new_retired)}


# ------------------------------------------------------------ the traffic


def warm_up(d: Harness) -> None:
    """Compile (or load from the cache) every program the window will run.

    A boundary admits as many requests as it has free slots and queued
    requests, which the engine pads to a power of two.  Closed loops fill
    every slot at once and run two supersteps: the admitting one and one
    as the window runs them; the admissions of fewer requests, which
    follow a chain's end, are compiled without running.  An open loop runs
    one wave of each power of two to completion.  Either loop's first
    batch of requests compiles the program that draws every batch's
    keys."""
    tr = d.tr
    if tr["loop"] == "closed":
        n = 1
        while n < int(tr["slots"]):
            d.compile_admission(n)
            n *= 2
        d.submit(list(range(int(tr["clients"]))))
        d.step()
        d.step()
        return
    n, rid = int(tr["slots"]), WARM_RID
    while n >= 1:
        d.submit(list(range(rid, rid + n)))
        rid += n
        while d.step():
            pass
        n //= 2
    d.completed.clear()


class TracedSpan:
    """The window's ``chipbench.window`` span; with a trace directory the
    profiler records from just before it opens until it closes."""

    def __init__(self, d: Harness, trace_dir: str | None):
        import jax

        self._jax, self.trace_dir = jax, trace_dir
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        self.span = d.annotate("chipbench.window")
        self.span.__enter__()
        self.open = True

    def close(self) -> None:
        if self.open:
            self.span.__exit__(None, None, None)
            self.open = False
            if self.trace_dir:
                self._jax.profiler.stop_trace()


def run_window(d: Harness, seconds: float, seed: int,
               trace_dir: str | None = None) -> dict:
    """Drive the engine for ``seconds`` from a superstep boundary to one;
    returns the window's work, times and request latencies.  With
    ``trace_dir`` the profiler records the window there."""
    import numpy as np

    from chipbench.traffic import open_schedule

    tr = d.tr
    out = {"supersteps": 0}
    if tr["loop"] == "open":
        due = open_schedule(tr, seed, seconds)
        keys = d.keys(np.arange(len(due)))
    s0 = d.snapshot()
    span = TracedSpan(d, trace_dir)
    t0 = time.perf_counter()
    if tr["loop"] == "closed":
        nxt = int(tr["clients"])  # warm-up sent rids 0 .. clients-1
        while time.perf_counter() - t0 < seconds:
            before = len(d.completed)
            d.step()
            out["supersteps"] += 1
            done = len(d.completed) - before
            if done:  # each finished caller sends its next request
                d.submit(list(range(nxt, nxt + done)))
                nxt += done
    else:
        sent, late, i = {}, [], 0
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            j = i
            while j < len(due) and t0 + due[j] <= now:
                j += 1
            if j > i:
                d.submit(list(range(i, j)), keys[i:j])
                for r in range(i, j):
                    sent[r] = t0 + due[r]
                    late.append(now - sent[r])
                i = j
            if d.has_work():
                d.step()
                out["supersteps"] += 1
            else:
                nxt_due = t0 + (due[i] if i < len(due) else seconds)
                time.sleep(max(0.0, min(nxt_due, t0 + seconds)
                               - time.perf_counter()))
    t1 = time.perf_counter()
    span.close()
    s1 = d.snapshot()
    out.update(window_s=t1 - t0, t0=t0, t1=t1,
               host_s=s1["host_s"] - s0["host_s"],
               work=window_work(s0, s1, d.K))
    if tr["loop"] == "open":
        out["in_flight_at_close"] = sum(r not in d.completed for r in sent)
        t_drain = time.perf_counter()
        while any(r not in d.completed for r in sent) and (
                time.perf_counter() - t_drain < float(tr["drain_s"])):
            if not d.step():
                break
        dropped = d.snapshot()["dropped"] - s0["dropped"]
        lat = [d.completed[r] - sent[r] if r in d.completed else math.inf
               for r in sent]
        out.update(latencies=lat, lateness=late, dropped=dropped,
                   deadline_s=float(tr["deadline_s"]))
    return out


# -------------------------------------------------------------- the check


def check_pairs(d: Harness) -> tuple:
    """(pre, post) states of the chains in the slots around supersteps of
    the timed program, run after the window.

    The check looks at fresh chains, whose early rounds accept whole
    windows often enough for the verify call's cached x0 to be read.  An
    open loop has drained by now, so it admits one fresh request per slot.
    A closed loop's window ends with every slot holding a chain that
    started at warm-up; with no caller sending more, those run to their
    end and the queued requests take their slots.  Either waits at most
    ``drain_s`` for the chains before it to leave."""
    tr = d.tr
    if tr["loop"] == "open":
        d.submit(list(range(CHECK_RID, CHECK_RID + int(tr["slots"]))))
    old = set(d.occupants()) - {CHECK_RID + i for i in range(int(tr["slots"]))}
    t0 = time.perf_counter()
    with d.annotate("chipbench.check_admit"):
        while (old & set(d.occupants()) or not d.occupants()) and (
                time.perf_counter() - t0 < float(tr["drain_s"])):
            if not d.step():
                break
    pre_all, post_all = [], []
    for _ in range(int(tr["check_supersteps"])):
        occ = d.occupants()
        pre = {r: d.chain(slot, full=True) for r, slot in occ.items()}
        pre = {r: c for r, c in pre.items() if int(c["a"]) < d.K}
        if not pre:
            break
        d.step()
        for r, c in pre.items():  # a retired chain keeps its slot's state
            pre_all.append(c)
            post_all.append(d.chain(occ[r], full=True))
    return pre_all, post_all


def run_check(cell: dict, pre: list, post: list) -> dict:
    """The reference's replay of ``pre`` compared with ``post``."""
    from chipbench.check import compare
    from chipbench.model import family, make_weights
    from chipbench.reference import ddpm_schedule, denoise_blocks

    cfg = cell["cfg"]
    w = make_weights(cfg)
    m, denoise = cfg["model"], family(cfg).denoise
    model = lambda t, y: denoise_blocks(denoise, w, t, y, m)  # noqa: E731
    sched = ddpm_schedule(int(cfg["K"]), cfg["beta_schedule"])
    return compare(pre, post, sched, int(cfg["theta"]),
                   int(cfg["lane"]["rounds_per_sync"]), model)


# ------------------------------------------------------------------- main


def finite(v):
    """JSON has no infinity: an infinite reading prints as 1e308."""
    return v if v is None or math.isfinite(v) else 1e308


def device_peaks(kind: str) -> dict:
    peaks = json.loads((HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise SystemExit(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             devices: list) -> dict:
    """One run of a cell on ``devices``: set-up, the window, the check.
    Returns the result object, the rows compared and the check's pairs."""
    from chipbench.check import judge
    from chipbench.model import family

    w, cfg, tr = cell["workload"], cell["cfg"], cell["traffic"]
    eng, layout, params = build(cell, devices)
    d = Harness(eng, cfg, tr, seed)
    warm_up(d)
    setup_s = time.perf_counter() - T_PROCESS
    trace_dir = None
    if trace:  # a trace of a whole window of small supersteps takes
        # minutes to write and read: a traced run measures at most TRACE_S
        trace_dir = str(TRACE_DIR / w["name"])
        seconds = min(seconds, TRACE_S)
    win = run_window(d, seconds, seed, trace_dir)
    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in devices)
    pre, post = check_pairs(d)
    del d, eng, params
    gc.collect()
    t_ref = time.perf_counter()
    numbers = run_check(cell, pre, post)
    ref_s = time.perf_counter() - t_ref
    correct, rows = judge(numbers, cfg["limits"])

    kind = devices[0].device_kind
    run = dict(win, setup_s=setup_s, cfg=cfg, traffic=tr,
               chips=len(devices), peak=device_peaks(kind), layout=layout,
               flops_per_point=family(cfg).flops(cfg["model"]))
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct)}
    if tr["loop"] == "open":
        result["attempted"] = len(win["latencies"])
        result["failed"] = int(sum(math.isinf(x) for x in win["latencies"]))
    else:  # every chain the window advanced; none can fail in a closed loop
        result["attempted"] = win["work"]["chains"]
        result["failed"] = 0
    if trace:
        from chipbench import trace as tracing

        events = tracing.read_xplane(
            tracing.find_xplane(str(TRACE_DIR / w["name"])))
        red = tracing.reduce(events, *tracing.span_ns(events, "window"))
        shutil.rmtree(TRACE_DIR / w["name"], ignore_errors=True)
        run["trace"] = red
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
    units = {m["name"]: m["unit"] for m in
             cell["bench"]["end_to_end"] + cell["bench"]["per_layer"]}
    metrics = {}
    for name in metric_names(cell["bench"], w["name"], trace):
        v = load_reader(name)(run)
        if v is not None:
            metrics[name] = {"value": finite(v), "unit": units[name]}
    result.update(metrics=metrics, device=device)
    if trace:
        red = run["trace"]
        ops = sorted(red["op_s"].items(), key=lambda x: -x[1])[:10]
        result["breakdown"] = {"device_ops": [[n.split(" = ")[0], s]
                                              for n, s in ops],
                               "idle_gaps": [[n, s] for n, s in
                                             red["idle_gaps"][:10]]}
    report(win, numbers, ref_s)
    result["checks"] = {n: {"value": finite(v if v is None else float(v)),
                            "limit": lim}
                        for n, v, lim in rows}
    return {"result": result, "rows": rows, "numbers": numbers,
            "pre": pre, "post": post}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    cell = load_cell(opts.workload)
    jax = configure_jax()
    try:
        devices = require_chips(jax, int(cell["workload"]["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}; this benchmark runs on the chip only",
              file=sys.stderr)
        return 2
    out = run_cell(cell, opts.seed, opts.seconds, bool(opts.trace), devices)
    for n, v, lim in out["rows"]:
        print(f"check {n}: {v if v is None else float(v)!r} (limit {lim!r})",
              file=sys.stderr)
    print(json.dumps(out["result"]), flush=True)
    return 0


def report(win: dict, numbers: dict, ref_s: float) -> None:
    """The run's facts that are no metric, on standard error."""
    import numpy as np

    wk = win["work"]
    log(f"window {win['window_s']:.4f}s, {win['supersteps']} supersteps, "
        f"{wk['steps']} steps committed in {wk['rounds']} chain rounds, "
        f"{wk['evals']} model evaluations, {wk['retired']} retired")
    if "lateness" in win:
        late = np.asarray(win["lateness"] or [0.0])
        lat = np.asarray(win["latencies"])
        met = float(np.mean(lat <= win["deadline_s"])) if len(lat) else 0.0
        log(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
            f"p95 {np.percentile(late, 95) * 1e3:.3f} ms, max "
            f"{late.max() * 1e3:.3f} ms; {len(lat)} requests, "
            f"{met:.4f} within {win['deadline_s']} s, "
            f"{win['in_flight_at_close']} in flight at the close, "
            f"{win['dropped']} dropped")
    log(f"check numbers {json.dumps(numbers)}; reference took {ref_s:.1f}s")


if __name__ == "__main__":
    sys.exit(main())
