"""Each metric reader on a run whose numbers are worked by hand, and the
benchmark's metrics each have a reader."""

import json
import types

import pytest

from chipbench import run as bench
from chipbench.tests.cpu_cell import ROOT

PEAK = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e9}
MODEL = {"seq_len": 2, "d_data": 5}  # D = 10


def a_run(**extra):
    r = {"setup_s": 12.5, "window_s": 2.0, "supersteps": 40, "host_s": 0.1,
         "work": {"steps": 300, "rounds": 120, "evals": 1000},
         "chips": 1, "peak": PEAK, "flops_per_point": 1e9,
         "cfg": {"model": MODEL}, "traffic": {"slots": 4},
         "layout": types.SimpleNamespace(budget=3)}
    r.update(extra)
    return r


def read(name, r):
    return bench.load_reader(name)(r)


def test_every_metric_has_a_reader():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(bench.load_reader(m["name"])), m["name"]


def test_end_to_end_readers():
    r = a_run(latencies=[0.1] * 90 + [0.5] * 9 + [float("inf")])
    assert read("setup_s", r) == 12.5
    assert read("steps_per_s", r) == 150.0
    assert read("latency_p50_s", r) == 0.1
    r = a_run(latencies=[0.1] * 40 + [float("inf")] * 60)
    assert read("latency_p50_s", r) == float("inf")  # most never completed
    assert read("latency_p50_s", a_run()) is None  # a closed loop


def test_per_layer_readers():
    r = a_run()
    for sfx in ("batch", "control"):
        assert read(f"host_ms_per_superstep.{sfx}", r) == pytest.approx(2.5)
        assert read(f"steps_per_round.{sfx}", r) == 2.5
        # 1000 points x 1e9 FLOP over 2 s x 1 chip x 100 TFLOP/s
        assert read(f"mfu.{sfx}", r) == pytest.approx(0.5)
        assert read(f"device_idle.{sfx}", r) is None  # no trace
        t = {"busy_s": 1.5, "window_s": 2.0, "op_s": {}, "op_n": {}}
        assert read(f"device_idle.{sfx}", a_run(trace=t)) == 25.0
