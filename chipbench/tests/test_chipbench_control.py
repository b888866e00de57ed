"""The control: the reference, put in the program's place and computed in
the precision below the configuration's, must fail the comparison.

At a size the CPU holds (the smoke model computes in float32, so the
control rounds the denoiser's matrix operands to bfloat16), one run
yields pre-states and the program's numbers; the control then replays the
same pre-states and is compared by the same rule.  Limits set between the
two readings pass the program and fail the control.  The control at the
cells' own sizes is ``chipbench/control.py``, run on the chip.
"""

import os
import subprocess
import sys

from chipbench.check import judge
from chipbench.control import control_numbers
from chipbench.tests import cpu_cell


def test_control_fails_where_the_program_passes(monkeypatch):
    cpu_cell.peaks(monkeypatch)
    c = cpu_cell.cell()
    seed = 2**31 + 99
    out = cpu_cell.run_on_cpu(c, seed=seed)
    prog = out["numbers"]
    ctrl = control_numbers(c, out["pre"])
    assert ctrl["chains"] == prog["chains"] > 0
    # the control stands at least 3x above the program on some number
    apart = [k for k in ("decision_gap", "y_rel_err", "x0_rel_err")
             if k in prog and k in ctrl and ctrl[k] >= 3 * max(prog[k], 1e-12)]
    assert apart, (prog, ctrl)
    limits = {k: (max(prog[k], 1e-12) * ctrl[k]) ** 0.5 for k in apart}
    assert judge(prog, limits)[0]
    assert not judge(ctrl, limits)[0]


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cpu_cell.ROOT / "chipbench" / "run.py"),
         "--workload", "ldm-dit.batch", "--seed", "3", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
