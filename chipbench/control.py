#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and its control's.

    python3 chipbench/control.py --workload ldm-dit.batch --seconds 5 --seeds 11 12 13

For every seed, in one process: one run of the cell (set-up, a short
window, the check pairs) gives the program's numbers; then the reference,
with its matrix products in the next precision below the configuration's
(its ``control`` key: float8 e4m3 operands for a bfloat16 model), is put
in the program's place on the same pre-states and compared by the same
rule and judged by the cell's limits: the control has to come out not
correct.  One JSON line per seed, then the largest program reading and the
smallest control reading of each number.  The benchmark's own runs never
run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from chipbench import run as bench  # noqa: E402


def control_numbers(cell: dict, pre: list) -> dict:
    from chipbench.check import compare, control_posts
    from chipbench.model import family, make_weights
    from chipbench.reference import ddpm_schedule, denoise_blocks

    cfg = cell["cfg"]
    w = make_weights(cfg)
    m, denoise = cfg["model"], family(cfg).denoise
    sched = ddpm_schedule(int(cfg["K"]), cfg["beta_schedule"])
    theta, R = int(cfg["theta"]), int(cfg["lane"]["rounds_per_sync"])
    low = lambda t, y: denoise_blocks(  # noqa: E731
        denoise, w, t, y, m, cfg["control"])
    ref = lambda t, y: denoise_blocks(denoise, w, t, y, m)  # noqa: E731
    return compare(pre, control_posts(pre, sched, theta, R, low), sched,
                   theta, R, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    opts = ap.parse_args(argv)
    cell = bench.load_cell(opts.workload)
    jax = bench.configure_jax()
    try:
        devices = bench.require_chips(jax, int(cell["workload"]["chips"]))
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from chipbench.check import judge

    worst, best, verdicts = {}, {}, []
    for seed in opts.seeds:
        out = bench.run_cell(cell, seed, opts.seconds, False, devices)
        prog = out["numbers"]
        ctrl = control_numbers(cell, out["pre"])
        ctrl_ok, rows = judge(ctrl, cell["cfg"]["limits"])
        verdicts.append(ctrl_ok)
        print(json.dumps({"seed": seed, "correct": out["result"]["correct"],
                          "control_correct": ctrl_ok, "program": prog,
                          "control": ctrl}), flush=True)
        for n, v, lim in rows:
            print(f"control {n}: {v!r} (limit {lim!r})", file=sys.stderr)
        for k, v in prog.items():
            worst[k] = max(worst.get(k, 0.0), v)
        for k, v in ctrl.items():
            best[k] = min(best.get(k, float("inf")), v)
    print(json.dumps({"program_max": worst, "control_min": best,
                      "control_correct_on_any_seed": any(verdicts)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
