"""A run whose timed path is broken underneath must come out incorrect.

Each test drives the whole harness past its look for a chip (set-up,
window, check) on a CPU-sized cell, with one fault planted in the
program: a superstep that returns its state unchanged, one that leaves
half of the slot batch out, a denoiser whose answer is altered where it
is produced, and a GRS that accepts every draft or a commit that skips
the reflection, both in the fused kernel that verifies and commits a
round (the policy cell's lane) and in the core GRS of the unpacked round
(the ldm-dit cell's).  (No cell exchanges data between chips, so there
is no exchange to leave out.)  Each run is judged by the limits of the
configuration whose lane it runs.
"""

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import cpu_cell


def break_superstep(monkeypatch, fault):
    """Wrap every superstep program the engine builds: ``fault(old, new)``
    picks the states it returns."""
    build = run.build

    def broken(*a, **k):
        eng, layout, params = build(*a, **k)
        make = eng._make_superstep

        def make_broken(R, budget):
            fn = make(R, budget)

            def step(states, *rest):
                new, sync = fn(states, *rest)
                return fault(states, new), sync
            return step
        eng._make_superstep = make_broken
        return eng, layout, params

    monkeypatch.setattr(run, "build", broken)


KERNEL_LANE = {"execution": "packed", "round_impl": "fused",
               "pack_impl": "kernel", "grs_impl": "kernel",
               "rounds_per_sync": 2}
OPEN = {"loop": "open", "clients": 16, "period_s": 0.25, "deadline_s": 1.0,
        "slots": 4, "drain_s": 5, "check_supersteps": 6}


@pytest.fixture
def sound(monkeypatch):
    cpu_cell.peaks(monkeypatch)
    jax.clear_caches()  # no program traced before a fault is planted
    yield
    jax.clear_caches()


def break_grs(monkeypatch, lane, fault):
    """Plant ``fault(z, accept, mh, mt, sig, xi)`` -> (z, accept) behind the
    GRS accept test and commit of ``lane``: the fused verify/commit kernel
    (the policy cell's lane) or the core GRS of the unpacked round (the
    ldm-dit cell's).  Returns the CPU cell of that lane, with the limits of
    the configuration that runs it."""
    if lane == "kernel":
        import repro.kernels.superstep.kernel as fused

        select = fused.grs_select

        def broken(u, sig, xi, mh, mt, vnorm2, vdotxi):
            z, accept = select(u, sig, xi, mh, mt, vnorm2, vdotxi)
            return fault(z, accept, mh, mt, sig, xi)

        monkeypatch.setattr(fused, "grs_select", broken)
        return cpu_cell.cell(lane=KERNEL_LANE, traffic=OPEN, K=24)
    import repro.core.asd as asd

    grs = asd.grs

    def broken_core(u, xi, m_hat, m, sigma, event_ndim=1):
        z, accept = grs(u, xi, m_hat, m, sigma, event_ndim)
        sig = asd.bcast_right(sigma, xi.ndim)
        acc = asd.bcast_right(accept, xi.ndim)
        z, acc = fault(z, acc, m_hat, m, sig, xi)
        return z, acc.reshape(accept.shape).astype(accept.dtype)

    monkeypatch.setattr(asd, "grs", broken_core)
    return cpu_cell.cell(config="ldm-dit")


def test_sound_run_is_correct(sound):
    out = cpu_cell.run_on_cpu(cpu_cell.cell())
    assert out["result"]["correct"], out["rows"]
    assert out["numbers"]["chains"] > 0


def test_state_returned_unchanged_is_caught(sound, monkeypatch):
    break_superstep(monkeypatch, lambda old, new: old)
    out = cpu_cell.run_on_cpu(cpu_cell.cell())
    assert not out["result"]["correct"]


def test_half_the_batch_left_out_is_caught(sound, monkeypatch):
    def half(old, new):
        return jax.tree_util.tree_map(
            lambda o, n: jnp.concatenate([n[:2], o[2:]]), old, new)

    break_superstep(monkeypatch, half)
    out = cpu_cell.run_on_cpu(cpu_cell.cell())
    assert not out["result"]["correct"]


def test_altered_answer_is_caught(sound, monkeypatch):
    import repro.launch.serve as serve

    make = serve.make_ddpm_model_fn

    def altered(*a, **k):
        fn = make(*a, **k)
        return lambda t, y: fn(t, y).at[..., 0, 0].add(0.5)

    monkeypatch.setattr(serve, "make_ddpm_model_fn", altered)
    out = cpu_cell.run_on_cpu(cpu_cell.cell())
    assert not out["result"]["correct"]


def test_sound_kernel_lane_is_correct(sound):
    out = cpu_cell.run_on_cpu(
        cpu_cell.cell(lane=KERNEL_LANE, traffic=OPEN, K=24))
    assert out["result"]["correct"], out["rows"]
    assert out["numbers"]["reflect_points"] > 0


def test_sound_ldm_dit_limits_hold(sound):
    out = cpu_cell.run_on_cpu(cpu_cell.cell(config="ldm-dit"))
    assert out["result"]["correct"], out["rows"]


@pytest.mark.parametrize("lane", ["kernel", "core"])
def test_grs_that_accepts_every_draft_is_caught(sound, monkeypatch, lane):
    c = break_grs(monkeypatch, lane, lambda z, acc, mh, mt, sig, xi: (
        mh + sig * xi, jnp.ones_like(acc)))
    out = cpu_cell.run_on_cpu(c)
    assert not out["result"]["correct"], out["numbers"]


@pytest.mark.parametrize("lane", ["kernel", "core"])
def test_commit_without_the_reflection_is_caught(sound, monkeypatch, lane):
    c = break_grs(monkeypatch, lane, lambda z, acc, mh, mt, sig, xi: (
        jnp.where(acc > 0, z, mt + sig * xi), acc))
    out = cpu_cell.run_on_cpu(c)
    assert not out["result"]["correct"], out["numbers"]


def test_traced_run_measures_at_most_trace_s(sound, monkeypatch):
    monkeypatch.setattr(run, "TRACE_S", 0.2)
    out = cpu_cell.run_on_cpu(cpu_cell.cell(), seconds=0.8, trace=True)
    dev = out["result"]["device"]
    assert 0.2 <= dev["window_s"] < 0.6, dev
    assert out["result"]["correct"], out["rows"]
    assert dev["busy_s"] >= 0.0
