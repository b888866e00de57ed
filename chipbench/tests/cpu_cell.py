"""A benchmark cell small enough for the CPU: the registry's smoke-sized
diffusion-policy transformer through the same harness, on one CPU device.

Only tests use it.  It takes the place of ``run.load_cell`` and of the
look for a chip; the run itself (set-up, window, check) is the harness's.
"""

import copy
import json
import pathlib

import jax

from chipbench import run

ROOT = pathlib.Path(__file__).resolve().parents[2]

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "head_dim": 16, "d_ff": 128, "seq_len": 8, "d_data": 4,
         "time_dim": 256, "norm_eps": 1e-6, "compute_dtype": "float32",
         "param_dtype": "float32"}
UNPACKED = {"execution": "unpacked", "round_impl": "packed",
            "pack_impl": "ref", "grs_impl": "core", "rounds_per_sync": 1}
CLOSED = {"loop": "closed", "clients": 12, "slots": 4, "drain_s": 5,
          "check_supersteps": 8}


def cell(config: str = "policy", lane=None, traffic=None, K: int = 16):
    """The CPU cell: sizes of the smoke model, the limits of ``config``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{config}.json")
                     .read_text())
    cfg = dict(copy.deepcopy(cfg), name="cpu-smoke",
               registry_model="paper-diffusion-policy-smoke", model=MODEL,
               K=K, theta=4, lane=lane or UNPACKED, control="bfloat16")
    cfg["weights"] = dict(cfg["weights"], out_proj_scale=0.03125)
    bench["workloads"].append({"name": "cpu.smoke", "config": "cpu-smoke",
                               "traffic": "cpu", "chips": 1, "why": "test"})
    return {"bench": bench, "workload": bench["workloads"][-1], "cfg": cfg,
            "traffic": dict(traffic or CLOSED)}


def run_on_cpu(c, seed=2**31 + 77, seconds=0.5, trace=False):
    jax.config.update("jax_enable_compilation_cache", False)
    return run.run_cell(c, seed, seconds, trace, jax.devices()[:1])


def peaks(monkeypatch):
    monkeypatch.setattr(run, "device_peaks", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
