"""The trace reduction of chipbench/trace.py on small traces."""

import pathlib

import pytest

from chipbench import trace

# A serialized XSpace in the profiler's format: two TPU planes and the host
# plane with the harness's spans (made with ProfileData's text-proto
# serializer; times in the comments below are nanoseconds).
FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "two_devices.xplane.pb"


def synthetic():
    dev = "/device:TPU:0"
    ops = [("fusion.1", 100, 50), ("fusion.1", 200, 50),
           ("conv.2", 120, 100),  # overlaps fusion.1 at 120..150
           ("copy.3", 400, 100)]  # straddles the window's end at 450
    spans = [("chipbench.window", 50, 400), ("chipbench.step", 60, 330),
             ("chipbench.submit", 350, 60), ("other", 0, 1000)]
    return {"devices": {dev: ops}, "spans": spans}


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_busy_union_clipped_to_the_window():
    t = synthetic()
    t0, t1 = trace.span_ns(t, "window")
    assert (t0, t1) == (50, 450)
    red = trace.reduce(t, t0, t1)
    # busy: [100, 250) merged from fusion/conv/fusion, [400, 450) clipped
    assert red["busy_s"] == pytest.approx(200e-9)
    assert red["window_s"] == pytest.approx(400e-9)


def test_op_times_and_calls():
    red = trace.reduce(synthetic(), 50, 450)
    assert red["op_s"]["fusion.1"] == pytest.approx(100e-9)
    assert red["op_n"]["fusion.1"] == 2
    assert red["op_s"]["conv.2"] == pytest.approx(100e-9)
    assert red["op_s"]["copy.3"] == pytest.approx(50e-9)  # clipped


def test_idle_gaps_labelled_by_innermost_harness_span():
    red = trace.reduce(synthetic(), 50, 450)
    # gaps: [50,100) in step, [250,400) midpoint 325 in step, [400..] busy
    gaps = red["idle_gaps"]
    assert gaps[0] == ("step", pytest.approx(150e-9))
    assert gaps[1] == ("step", pytest.approx(50e-9))
    assert len(gaps) == 2


def test_no_device_reads_zero_busy():
    red = trace.reduce({"devices": {}, "spans": []}, 0, 100)
    assert red["busy_s"] == 0.0 and red["idle_gaps"] == []


def test_recorded_trace_reduces():
    t = trace.read_xplane(str(FIXTURE))
    assert sorted(t["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    red = trace.reduce(t, *trace.span_ns(t, "window"))  # [1000, 2000)
    assert red["window_s"] == pytest.approx(1000e-9)
    # TPU:0 busy [1000, 1300) u [1500, 1600) u [1700, 1800); TPU:1 600 ns
    assert red["busy_by_device"]["/device:TPU:0"] == pytest.approx(500e-9)
    assert red["busy_by_device"]["/device:TPU:1"] == pytest.approx(600e-9)
    assert red["busy_s"] == pytest.approx(550e-9)
    assert red["op_s"]["fusion.7"] == pytest.approx(1000e-9)
    assert red["op_n"]["fusion.7"] == 3
    # the kernel: two calls of 100 ns
    assert red["op_s"]["_fused_commit_kernel"] == pytest.approx(200e-9)
    assert red["op_n"]["_fused_commit_kernel"] == 2
    # only the harness's spans label gaps, the innermost one winning
    assert [g for g, _ in red["idle_gaps"]] == ["step", "window", "submit"]
    assert [s for _, s in red["idle_gaps"]] == pytest.approx(
        [200e-9, 200e-9, 100e-9])
