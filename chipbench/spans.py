"""Attribution of a profiler trace to the serving program's own names:
device time per ``jax.named_scope`` of the round (``asd/propose``,
``asd/verify``, ...), host time per program span (``repro.step``,
``repro.admit``, ...), and each device idle gap put down to the innermost
span, program or harness, that covers it.

It reads the same ``.xplane.pb`` as ``chipbench/trace.py`` and leaves that
reduction as it is: ``read_program`` also returns the harness's view, so
``trace.reduce`` gives the accepted numbers from the same read.

- A device op's scope is the innermost ``asd/<phase>`` of its op-name
  metadata, resolved once per op name: from the op's own stats in the
  trace where they carry it (``tf_op``), else from the compiled programs'
  HLO text (``hlo_scopes``), matched by instruction name.  Only leaf ops
  count towards a scope: ops that enclose no other op on the ``XLA Ops``
  line, so a ``while`` and its body are not counted twice.
- A span's self time is its duration less what its direct children cover.
- A profiler annotation folds its arguments into its name as
  ``name#k=v#``: names are cut at ``#``.
"""

from __future__ import annotations

import re

from chipbench.trace import DEVICE_PREFIX, OPS_LINE, SPAN_PREFIX, union

PROGRAM_PREFIX = "repro."
SCOPE = re.compile(r"(?:^|[/(])(asd/[A-Za-z0-9_]+)")
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"')
# stats that may hold an op's name-scope path, most likely first
SCOPE_STATS = ("tf_op", "long_name", "name")


def scope_of(stats) -> str:
    """The innermost ``asd/<phase>`` in an op's metadata stats, or ''."""
    values = dict(stats)
    texts = [values[k] for k in SCOPE_STATS if isinstance(values.get(k), str)]
    texts += [v for v in values.values() if isinstance(v, str)]
    for text in texts:
        found = SCOPE.findall(text)
        if found:
            return found[-1]
    return ""


def op_key(name: str) -> str:
    """An op's name as the trace and the HLO text both give it."""
    return name.split(" = ")[0].lstrip("%_")


def hlo_scopes(*hlo_texts: str) -> dict:
    """{instruction: scope} from compiled HLO text, first text first."""
    out = {}
    for text in hlo_texts:
        for line in text.splitlines():
            m = HLO_OP.match(line)
            if m:
                found = SCOPE.findall(m.group(2))
                out.setdefault(op_key(m.group(1)),
                               found[-1] if found else "")
    return out


def read_program(path: str, hlo: dict | None = None) -> dict:
    """One pass over a trace file: {"devices": {plane: [(op, start_ns,
    dur_ns)]}, "scopes": {op: scope}, "op_stats": {op: [(stat, value)]} of
    each op's first event, "spans": [(name, start_ns, dur_ns, line)] with
    every ``repro.`` and ``chipbench.`` host span, and "harness": the
    ``chipbench/trace.py`` view ({"devices", "spans"}).  ``hlo``
    ({instruction: scope}) resolves ops whose stats name no scope."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, scopes, op_stats, spans = {}, {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = e.name
                    if name not in scopes:
                        op_stats[name] = list(e.stats)
                        scopes[name] = (scope_of(op_stats[name]) or
                                        (hlo or {}).get(op_key(name), ""))
                    ops.append((name, e.start_ns, e.duration_ns))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    name = e.name
                    if name.startswith((PROGRAM_PREFIX, SPAN_PREFIX)):
                        spans.append((name.split("#", 1)[0], e.start_ns,
                                      e.duration_ns, (plane.name, i)))
    harness = [(n, s, d) for n, s, d, _ in spans if n.startswith(SPAN_PREFIX)]
    return {"devices": devices, "scopes": scopes, "op_stats": op_stats,
            "spans": spans,
            "harness": {"devices": devices, "spans": harness}}


def leaf_ops(ops) -> list:
    """(op, start, end) of the ops that enclose no other op."""
    ivs = sorted(((s, -(s + d), n) for n, s, d in ops))
    out = []
    for i, (s, neg_e, n) in enumerate(ivs):
        e = -neg_e
        if i + 1 < len(ivs) and ivs[i + 1][0] < e:
            continue
        out.append((n, s, e))
    return out


def _span_tree(spans, t0, t1):
    """Total, self and longest time and count per program span starting in
    [t0, t1), clipped to the window."""
    by_line = {}
    for name, s, d, line in spans:
        by_line.setdefault(line, []).append((s, -(s + d), name))
    out = {}
    for ivs in by_line.values():
        ivs.sort()
        stack = []  # [end, name, child_ns] of the open ancestors
        done = []
        for s, neg_e, name in ivs:
            e = -neg_e
            while stack and stack[-1][0] <= s:
                done.append(stack.pop())
            node = [e, name, 0, s]
            if stack:
                parent = stack[-1]
                parent[2] += min(e, parent[0]) - s
            stack.append(node)
        done.extend(stack)
        for e, name, child, s in done:
            if not name.startswith(PROGRAM_PREFIX) or not t0 <= s < t1:
                continue
            dur = min(e, t1) - s
            rec = out.setdefault(name, {"total_s": 0.0, "self_s": 0.0,
                                        "max_s": 0.0, "n": 0})
            rec["total_s"] += dur * 1e-9
            rec["self_s"] += max(dur - child, 0) * 1e-9
            rec["max_s"] = max(rec["max_s"], dur * 1e-9)
            rec["n"] += 1
    return out


def _innermost_segments(spans, t0, t1):
    """[(start, end, label)] over [t0, t1): each piece of time labelled by
    the innermost host span open over it ('none' where none is)."""
    marks = []
    for k, (name, s, d, _) in enumerate(spans):
        marks.append((s, 1, -d, k))
        marks.append((s + d, 0, 0, k))
    marks.sort()
    out, open_, prev = [], [], t0
    for t, opening, _, k in marks:
        if t > prev:
            lo, hi = max(prev, t0), min(t, t1)
            if hi > lo:
                label = spans[open_[-1]][0] if open_ else "none"
                out.append((lo, hi, label))
            prev = t
        if opening:
            open_.append(k)
        else:
            open_.remove(k)
    if t1 > prev:
        out.append((max(prev, t0), t1,
                    spans[open_[-1]][0] if open_ else "none"))
    return out


def _overlap(a, b) -> list:
    """Pieces of sorted disjoint intervals ``a`` that lie in sorted disjoint
    intervals ``b``: [(start, end, index into a)]."""
    out, j = [], 0
    for i, (s, e, *_) in enumerate(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi, i))
            k += 1
    return out


def reduce_program(trace: dict, t0_ns: float, t1_ns: float,
                   top: int = 10) -> dict:
    """The program's attribution of the window [t0, t1):

    - ``scope_s``: leaf device seconds per scope ('' for unscoped ops),
      summed over devices; ``leaf_s`` their total; ``busy_s`` the mean
      busy time per device, as ``trace.reduce`` gives it.
    - ``span_s``: {span: {total_s, self_s, max_s, n}} per program span.
    - ``idle_by_span``: device 0's idle seconds per innermost covering
      span, program or harness ('none' where no span is open).
    - ``step_idle_unattributed``: % of device 0's idle time inside
      ``chipbench.step`` that no program span covers.
    - ``idle_gaps``: device 0's ``top`` longest idle gaps, each labelled
      by the innermost span over its midpoint.
    - ``top_ops``: the ``top`` ops with the most leaf time, with scopes;
      ``top_unscoped``: the same among ops under no scope.
    """
    scope_s, op_leaf, busy, idle = {}, {}, [], []
    for i, dev in enumerate(sorted(trace["devices"])):
        ops = [(n, s, d) for n, s, d in trace["devices"][dev]
               if s + d > t0_ns and s < t1_ns]
        for name, s, e in leaf_ops(ops):
            dur = (min(e, t1_ns) - max(s, t0_ns)) * 1e-9
            sc = trace["scopes"].get(name, "")
            scope_s[sc] = scope_s.get(sc, 0.0) + dur
            op_leaf[name] = op_leaf.get(name, 0.0) + dur
        merged = union([(max(s, t0_ns), min(s + d, t1_ns))
                        for _, s, d in ops])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        if i == 0:
            edges = [t0_ns] + [x for iv in merged for x in iv] + [t1_ns]
            idle = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    spans = sorted(trace["spans"], key=lambda x: (x[1], -x[2]))
    segs = _innermost_segments(spans, t0_ns, t1_ns)
    idle_by = {}
    for lo, hi, k in _overlap(segs, idle):
        label = segs[k][2]
        idle_by[label] = idle_by.get(label, 0.0) + (hi - lo) * 1e-9
    steps = union([(s, s + d) for n, s, d, _ in spans
                   if n == SPAN_PREFIX + "step"])
    prog = union([(s, s + d) for n, s, d, _ in spans
                  if n.startswith(PROGRAM_PREFIX)])
    idle_in_step = [(lo, hi) for lo, hi, _ in _overlap(idle, steps)]
    in_step = sum(hi - lo for lo, hi in idle_in_step)
    covered = sum(hi - lo for lo, hi, _ in _overlap(idle_in_step, prog))
    gaps = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    labelled = []
    for s, e in gaps:
        mid = (s + e) / 2
        label, width = "none", float("inf")
        for name, ss, dd, _ in spans:
            if ss <= mid <= ss + dd and dd < width:
                label, width = name, dd
        labelled.append((label, (e - s) * 1e-9))
    top_ops = sorted(op_leaf.items(), key=lambda x: -x[1])
    unscoped = [(n, s) for n, s in top_ops if not trace["scopes"].get(n)]
    return {
        "window_s": (t1_ns - t0_ns) * 1e-9,
        "busy_s": sum(busy) / max(len(busy), 1),
        "leaf_s": sum(scope_s.values()),
        "scope_s": scope_s,
        "span_s": _span_tree(spans, t0_ns, t1_ns),
        "idle_by_span": idle_by,
        "step_idle_unattributed": (
            100.0 * (in_step - covered) / in_step if in_step else None),
        "idle_gaps": labelled,
        "top_ops": [(n, trace["scopes"].get(n, ""), s)
                    for n, s in top_ops[:top]],
        "top_unscoped": unscoped[:top],
    }


def per_superstep(red: dict, supersteps: int) -> dict:
    """The per-layer numbers of one window, per superstep it drove:
    device ms under the verify, proposal and GRS scopes; host ms in
    admission, and in ``repro.step`` less its device wait; and the share of
    idle time inside the harness's step that no program span names."""
    if not supersteps:
        return {}
    sc, sp = red["scope_s"], red["span_s"]

    def total(name):
        return sp.get(PROGRAM_PREFIX + name, {}).get("total_s", 0.0)

    ms = 1e3 / supersteps
    return {
        "verify_call_ms": sc.get("asd/verify", 0.0) * ms,
        "proposal_call_ms": sc.get("asd/propose", 0.0) * ms,
        "grs_ms": sc.get("asd/grs", 0.0) * ms,
        "admit_ms_per_superstep": total("admit") * ms,
        "step_host_ms": (total("step") - total("device_wait")) * ms,
        "step_idle_unattributed": red["step_idle_unattributed"],
    }
