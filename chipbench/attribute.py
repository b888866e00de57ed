#!/usr/bin/env python3
"""Put a cell's time down to the program's own names, on the chip.

    python3 chipbench/attribute.py --workload policy.control --seed 7

Builds and warms the cell as ``chipbench/run.py`` does, drives one
untraced window and then one traced window of ``--seconds`` each, and
reduces the trace twice: with ``chipbench/trace.py`` (the accepted
numbers) and with ``chipbench/spans.py`` (device time per ``asd/*`` scope,
host time per ``repro.*`` span, idle time per innermost span).  It runs no
correctness check and reports no end-to-end metric; the cells' metrics come
from ``run.py``.  The last line of standard output is one JSON object;
``--keep`` leaves the trace file in place and names it there.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

T_PROCESS = time.perf_counter()

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TRACE_DIR = ROOT / ".chipbench_trace"


def attribute(cell: dict, seed: int, seconds: float, devices: list,
              trace_dir: str, keep: bool = False) -> dict:
    """The untraced and the traced window of one warmed engine, and the
    traced window's reductions."""
    from chipbench import run, spans, trace

    eng, _, _ = run.build(cell, devices)
    d = run.Harness(eng, cell["cfg"], cell["traffic"], seed)
    run.warm_up(d)
    setup_s = time.perf_counter() - T_PROCESS
    plain = run.run_window(d, seconds, seed)
    d.completed.clear()
    t_wall = time.perf_counter()
    traced = run.run_window(d, seconds, seed, trace_dir)
    path = trace.find_xplane(trace_dir)
    t_read = time.perf_counter()
    events = spans.read_program(path, spans.hlo_scopes(*program_hlo(eng)))
    t0, t1 = trace.span_ns(events["harness"], "window")
    accepted = trace.reduce(events["harness"], t0, t1)
    red = spans.reduce_program(events, t0, t1)
    read_s = time.perf_counter() - t_read
    if not keep:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out = {
        "workload": cell["workload"]["name"], "seed": seed,
        "device": devices[0].device_kind, "setup_s": setup_s,
        "supersteps_per_s": {
            "untraced": plain["supersteps"] / plain["window_s"],
            "traced": traced["supersteps"] / traced["window_s"]},
        "traced_window_wall_s": time.perf_counter() - t_wall,
        "trace_read_s": read_s,
        "supersteps": traced["supersteps"],
        "host_ms_per_superstep": (traced["host_s"] * 1e3
                                  / max(traced["supersteps"], 1)),
        "device_idle": 100.0 * (1.0 - accepted["busy_s"]
                                / accepted["window_s"]),
        "per_superstep": spans.per_superstep(red, traced["supersteps"]),
        "program": red,
        "op_stats": {n: {k: v if isinstance(v, (int, float)) else str(v)
                         for k, v in events["op_stats"].get(n, [])}
                     for n, _, _ in red["top_ops"][:3]},
    }
    if keep:
        out["trace_file"] = path
    return out


def program_hlo(eng) -> list:
    """Compiled HLO text of the engine's superstep programs, then of its
    admission scatters: where the trace's own stats name no scope, op names
    are matched against these (a cache hit each, after the windows)."""
    import jax
    import numpy as np

    args = [eng._states, eng._conds, eng._params, eng._weights_dev]
    texts = []
    for (_, budget), fn in sorted(eng._superstep_fns.items(), key=str):
        extra = [np.int32(eng._budget_cap)] if budget == "data" else []
        texts.append(fn.lower(*args, *extra).compile().as_text())
    for width in sorted(eng._admit_widths):
        y0s = jax.ShapeDtypeStruct((width,) + eng.event_shape, np.float32)
        keys = jax.ShapeDtypeStruct((width, 2), np.uint32)
        idxs = jax.ShapeDtypeStruct((width,), np.int32)
        texts.append(eng._admit_fn.lower(eng._states, y0s, keys, idxs)
                     .compile().as_text())
    return texts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--keep", action="store_true")
    opts = ap.parse_args(argv)
    from chipbench import run

    cell = run.load_cell(opts.workload)
    jax = run.configure_jax()
    try:
        devices = run.require_chips(jax, int(cell["workload"]["chips"]))
    except run.NoChip as e:
        print(f"attribute: {e}; this tool runs on the chip only",
              file=sys.stderr)
        return 2
    out = attribute(cell, opts.seed, opts.seconds, devices,
                    str(TRACE_DIR / f"attribute-{opts.workload}"), opts.keep)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
