"""The program's attribution of a trace (chipbench/spans.py) on a small
recorded trace, and the accepted reduction of chipbench/trace.py on it."""

import pathlib

import pytest

from chipbench import spans, trace

# A serialized XSpace in the profiler's format (made with ProfileData's
# text-proto serializer from the tables below by ``write_fixture``): one TPU
# plane whose ops carry their name-scope path in a ``tf_op`` stat, and the
# host plane with two supersteps of program spans inside the harness's
# spans.  Times are nanoseconds.  A TPU v5e trace names its ops by their
# HLO text and carries no such stat; ``write_fixture(with_stats=False)``
# makes that variant, whose scopes come from the HLO.
FIXTURE = (pathlib.Path(__file__).parent / "fixtures"
           / "program_spans.xplane.pb")

HOST = [
    ("chipbench.window", 1000, 3000),
    ("chipbench.step", 1100, 1900),
    ("repro.step#superstep=7#", 1150, 1850),
    ("repro.admit#admitted=1#", 1150, 1300),
    ("repro.compile#program=admit[1]#", 1200, 1290),
    ("repro.dispatch#R=2#", 1300, 1350),
    ("repro.device_wait", 1350, 1700),
    ("repro.harvest", 1700, 1800),
    ("chipbench.step", 2000, 2800),
    ("repro.step#superstep=8#", 2000, 2700),
    ("repro.admit", 2000, 2050),
    ("repro.dispatch#R=2#", 2050, 2100),
    ("repro.device_wait", 2100, 2500),
    ("repro.harvest", 2500, 2650),
    ("chipbench.submit", 2850, 2950),
    ("PjitFunction(_superstep)", 1300, 1340),  # not a span: not read
]
TF_OP = {
    "while.1": "jit(_superstep)/while",
    "fusion.2": "jit(_superstep)/while/body/closed_call/asd/verify/dot",
    "fusion.3": ("jit(_superstep)/while/body/closed_call/vmap(asd/grs)/"
                 "add"),
    "fused_verify_commit.4": (
        "jit(_superstep)/while/body/closed_call/asd/grs/"
        "jit(fused_verify_commit_pallas)/fused_verify_commit"),
    "copy.5": "jit(_superstep)/copy",
    "fusion.6": "jit(_admit)/asd/admit/scatter",
}
DEVICE = [
    ("fusion.6", 1210, 1280),
    ("while.1", 1360, 1690), ("fusion.2", 1360, 1500),
    ("fusion.3", 1500, 1600), ("fused_verify_commit.4", 1600, 1690),
    ("copy.5", 1690, 1700),
    ("while.1", 2110, 2490), ("fusion.2", 2110, 2300),
    ("fusion.3", 2300, 2400), ("fused_verify_commit.4", 2400, 2490),
]


def hlo_line(name: str) -> str:
    """How a TPU trace names a device op: its HLO instruction's text."""
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p)"


def write_fixture(path, with_stats: bool = True) -> None:
    """The fixture, or (``with_stats=False``) the same trace as a TPU
    writes it: ops named by their HLO text, with no ``tf_op`` stat."""
    from jax.profiler import ProfileData

    def events(rows, ids, stat):
        out = ""
        for name, s, e in rows:
            st = (f' stats {{ metadata_id: 1 str_value: "{stat(name)}" }}'
                  if stat else "")
            out += (f"    events {{ metadata_id: {ids[name]} offset_ps: "
                    f"{s * 1000} duration_ps: {(e - s) * 1000}{st} }}\n")
        return out

    def metadata(ids, show=lambda n: n):
        return "".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{show(n)}" }} }}\n' for n, i in ids.items())

    hid = {n: i + 1 for i, n in enumerate(sorted({h[0] for h in HOST}))}
    oid = {n: i + 1 for i, n in enumerate(TF_OP)}
    text = ('planes {\n  id: 1\n  name: "/host:CPU"\n  lines {\n    id: 1\n'
            '    name: "python"\n    timestamp_ns: 0\n'
            + events(HOST, hid, None) + "  }\n" + metadata(hid) + "}\n"
            'planes {\n  id: 2\n  name: "/device:TPU:0"\n  lines {\n'
            '    id: 2\n    name: "XLA Ops"\n    timestamp_ns: 0\n'
            + events(DEVICE, oid, TF_OP.get if with_stats else None)
            + "  }\n" + metadata(oid, str if with_stats else hlo_line)
            + '  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }\n}\n')
    pathlib.Path(path).write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))


@pytest.fixture(scope="module")
def red():
    t = spans.read_program(str(FIXTURE))
    return spans.reduce_program(t, *trace.span_ns(t["harness"], "window"))


def test_fixture_holds_its_recipe():
    t = spans.read_program(str(FIXTURE))
    want = sorted((n.split("#")[0], s, e - s) for n, s, e in HOST
                  if n.startswith(("repro.", "chipbench.")))
    assert sorted((n, s, d) for n, s, d, _ in t["spans"]) == want
    assert t["devices"] == {"/device:TPU:0": [(n, s, e - s)
                                              for n, s, e in DEVICE]}
    assert t["scopes"] == {
        "while.1": "", "fusion.2": "asd/verify", "fusion.3": "asd/grs",
        "fused_verify_commit.4": "asd/grs", "copy.5": "",
        "fusion.6": "asd/admit"}


def test_accepted_reduction_reads_the_same():
    """The harness's view of a program trace reduces as trace.py's own
    reader does: program spans change no accepted number."""
    t = spans.read_program(str(FIXTURE))
    own = trace.read_xplane(str(FIXTURE))
    window = trace.span_ns(own, "window")
    assert trace.reduce(t["harness"], *window) == trace.reduce(own, *window)
    red = trace.reduce(own, *window)
    assert red["busy_s"] == pytest.approx(790e-9)
    assert [g for g, _ in red["idle_gaps"]] == ["step", "window", "step",
                                                "step"]


def test_scope_time_counts_leaf_ops_only(red):
    # the while ops enclose the verify/GRS ops: counted once, by the leaves
    assert red["scope_s"] == pytest.approx({
        "asd/verify": 330e-9, "asd/grs": 380e-9, "asd/admit": 70e-9,
        "": 10e-9})
    assert red["leaf_s"] == pytest.approx(red["busy_s"])
    assert red["top_ops"][0] == ("fusion.2", "asd/verify",
                                 pytest.approx(330e-9))
    assert red["top_unscoped"] == [("copy.5", pytest.approx(10e-9))]


def test_span_self_time(red):
    sp = red["span_s"]
    assert sp["repro.step"] == pytest.approx(
        {"total_s": 1400e-9, "self_s": 100e-9, "max_s": 700e-9, "n": 2})
    assert sp["repro.admit"] == pytest.approx(
        {"total_s": 200e-9, "self_s": 110e-9, "max_s": 150e-9, "n": 2})
    assert sp["repro.compile"] == pytest.approx(
        {"total_s": 90e-9, "self_s": 90e-9, "max_s": 90e-9, "n": 1})
    assert sp["repro.device_wait"]["total_s"] == pytest.approx(750e-9)
    assert "chipbench.step" not in sp


def test_idle_put_down_to_the_innermost_span(red):
    idle = red["idle_by_span"]
    assert idle == pytest.approx({
        "chipbench.window": 300e-9, "chipbench.step": 200e-9,
        "chipbench.submit": 100e-9, "repro.step": 100e-9,
        "repro.admit": 110e-9, "repro.compile": 20e-9,
        "repro.dispatch": 100e-9, "repro.device_wait": 30e-9,
        "repro.harvest": 250e-9})
    assert sum(idle.values()) == pytest.approx(2000e-9 - 790e-9)
    assert red["idle_gaps"] == [
        ("chipbench.step", pytest.approx(510e-9)),
        ("chipbench.window", pytest.approx(410e-9)),
        ("chipbench.step", pytest.approx(210e-9)),
        ("repro.dispatch", pytest.approx(80e-9))]
    # idle inside the harness's steps: 810 ns, of which 200 ns lie outside
    # every program span
    assert red["step_idle_unattributed"] == pytest.approx(100 * 200 / 810)


def test_per_superstep(red):
    got = spans.per_superstep(red, 2)
    assert got == pytest.approx({
        "verify_call_ms": 165e-6, "proposal_call_ms": 0.0,
        "grs_ms": 190e-6, "admit_ms_per_superstep": 100e-6,
        "step_host_ms": 325e-6,
        "step_idle_unattributed": 100 * 200 / 810})
    assert spans.per_superstep(red, 0) == {}


def test_scopes_from_hlo_where_the_trace_has_no_stats(tmp_path):
    write_fixture(tmp_path / "bare.xplane.pb", with_stats=False)
    hlo = "\n".join(f'  {hlo_line(n)}, metadata={{op_name="{op}"}}'
                     for n, op in TF_OP.items())
    hlo += '\n  ROOT %tuple.9 = (f32[8]) tuple(%fusion.2)'
    t = spans.read_program(str(tmp_path / "bare.xplane.pb"),
                           spans.hlo_scopes(hlo))
    want = spans.read_program(str(FIXTURE))["scopes"]
    assert t["scopes"] == {hlo_line(n): sc for n, sc in want.items()}
    red = spans.reduce_program(t, 1000, 3000)
    assert red["scope_s"] == pytest.approx({
        "asd/verify": 330e-9, "asd/grs": 380e-9, "asd/admit": 70e-9,
        "": 10e-9})
    assert spans.op_key("_fusion.454") == spans.op_key(
        "%fusion.454 = f32[1]") == "fusion.454"


def test_no_device_leaves_scopes_empty():
    red = spans.reduce_program(
        {"devices": {}, "scopes": {}, "spans": []}, 0, 100)
    assert red["scope_s"] == {} and red["step_idle_unattributed"] is None
