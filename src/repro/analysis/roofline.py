"""Roofline terms from a compiled AOT program (TPU v5e target constants).

compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
memory term     = HLO_bytes_per_chip / HBM_bw
collective term = collective_bytes_per_chip / link_bw

Notes:
  * jax's ``compiled.cost_analysis()`` on the partitioned program reports
    *per-device* flops / bytes — no division by chip count needed.
  * collective bytes are not in cost_analysis; we parse the post-SPMD HLO
    and sum the result-shape bytes of every all-gather / all-reduce /
    reduce-scatter / all-to-all / collective-permute (shapes there are
    per-device too).  We record both the raw operand-byte sum (the brief's
    definition) and a ring-traffic estimate with per-op factors.
"""

from __future__ import annotations

import dataclasses
import re

# ---- TPU v5e constants (per chip) ----
PEAK_FLOPS_BF16 = 197e12  # FLOP/s
HBM_BW = 819e9  # B/s
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# traffic multiplier for a ring implementation, per output byte
_RING_FACTOR = {
    "all-gather": 1.0,  # output is the gathered tensor; (n-1)/n of it moves
    "all-reduce": 2.0,  # reduce-scatter + all-gather
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_WHILE_RE = re.compile(r"while\(.*?\)[^\n]*?condition=%?([\w\.\-]+)[^\n]*?body=%?([\w\.\-]+)")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")


def _split_computations(hlo_text: str) -> tuple[dict[str, list[str]], str]:
    """Computation name -> instruction lines.  HLO text puts computation
    headers at column 0 ending with '{'; instructions are indented."""
    comps: dict[str, list[str]] = {}
    entry = ""
    cur = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            hdr = line.strip()
            is_entry = hdr.startswith("ENTRY")
            if is_entry:
                hdr = hdr[len("ENTRY"):].strip()
            name = hdr.lstrip("%").split(" ", 1)[0].split("(", 1)[0]
            if not name or name == "HloModule":
                cur = None
                continue
            cur = name
            comps[cur] = []
            if is_entry:
                entry = name
            continue
        if cur is not None:
            s = line.strip()
            if s == "}":
                cur = None
            elif s:
                comps[cur].append(s)
    return comps, entry


def _trip_count(cond_lines: list[str]) -> int:
    """jax scans compare a s32 counter against a constant trip count."""
    consts = []
    for line in cond_lines:
        consts += [int(x) for x in _CONST_RE.findall(line)]
    return max(consts) if consts else 1


def _comp_multipliers(comps: dict[str, list[str]], entry: str) -> dict[str, float]:
    """Execution multiplier per computation: the product of enclosing
    while-loop trip counts (jax scans lower to while with known trips)."""
    children: dict[str, list[tuple[str, float]]] = {}
    for name, lines in comps.items():
        edges = []
        for line in lines:
            for m in _WHILE_RE.finditer(line):
                cond, body = m.group(1), m.group(2)
                trip = _trip_count(comps.get(cond, []))
                edges.append((body, float(trip)))
                edges.append((cond, float(trip)))
            # non-while calls keep the parent's multiplier
            for m in re.finditer(
                r"(?:calls|to_apply|branch_computations)=\{?%?([\w\.\-]+)", line
            ):
                tgt = m.group(1)
                if all(tgt != e[0] for e in edges):
                    edges.append((tgt, 1.0))
        children[name] = edges

    mult: dict[str, float] = {}
    if entry not in comps:
        return {k: 1.0 for k in comps}
    stack = [(entry, 1.0)]
    while stack:
        name, m = stack.pop()
        if mult.get(name, 0.0) >= m:
            continue
        mult[name] = m
        for child, trip in children.get(name, []):
            stack.append((child, m * trip))
    for name in comps:
        mult.setdefault(name, 1.0)
    return mult


_OP_CALL_RE = {
    op: re.compile(rf"=\s*(.+?)\s{op}(?:-start)?\(") for op in _COLLECTIVES
}


def collective_bytes(hlo_text: str, scale_by_trip_counts: bool = True) -> dict:
    """Per-device collective bytes from post-SPMD HLO text.

    Collectives inside scan/while bodies execute trip-count times but appear
    once in the text; with ``scale_by_trip_counts`` each op's bytes are
    multiplied by the product of its enclosing loops' trip counts (parsed
    from the loop-condition constants).  Tuple-result collectives (bundled
    gradient all-reduces) sum every element's bytes.
    """
    comps, entry = _split_computations(hlo_text)
    mult = _comp_multipliers(comps, entry) if scale_by_trip_counts else {}
    per_op: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    per_op_static: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    counts: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for name, lines in comps.items():
        m = mult.get(name, 1.0)
        for s in lines:
            if "=" not in s:
                continue
            for op in _COLLECTIVES:
                mm = _OP_CALL_RE[op].search(s)
                if mm:
                    b = _shape_bytes(mm.group(1))  # full (tuple) result type
                    per_op[op] += b * m
                    per_op_static[op] += b
                    counts[op] += 1
                    break
    raw = sum(per_op.values())
    ring = sum(per_op[k] * _RING_FACTOR[k] for k in per_op)
    return {
        "per_op": per_op,
        "per_op_static": per_op_static,
        "counts": counts,
        "raw_bytes": raw,
        "ring_bytes": ring,
        "static_bytes": sum(per_op_static.values()),
    }


@dataclasses.dataclass
class Roofline:
    flops_per_chip: float
    bytes_per_chip: float
    collective_raw: float
    collective_ring: float
    coll_counts: dict
    coll_per_op: dict

    @property
    def t_compute(self):
        return self.flops_per_chip / PEAK_FLOPS_BF16

    @property
    def t_memory(self):
        return self.bytes_per_chip / HBM_BW

    @property
    def t_collective(self):
        return self.collective_ring / ICI_BW

    @property
    def dominant(self):
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    def bound_time(self):
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self):
        return {
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "collective_raw_bytes": self.collective_raw,
            "collective_ring_bytes": self.collective_ring,
            "coll_counts": self.coll_counts,
            "coll_per_op": self.coll_per_op,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
        }


def analyze(compiled) -> Roofline:
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return Roofline(
        flops_per_chip=float(cost.get("flops", 0.0)),
        bytes_per_chip=float(cost.get("bytes accessed", 0.0)),
        collective_raw=coll["raw_bytes"],
        collective_ring=coll["ring_bytes"],
        coll_counts=coll["counts"],
        coll_per_op=coll["per_op"],
    )


def model_flops(n_params: int, n_tokens: int, kind: str = "train",
                n_active_params: int | None = None) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); 2*N*D for a forward pass."""
    n = n_active_params if n_active_params is not None else n_params
    per_tok = 6 * n if kind == "train" else 2 * n
    return float(per_tok) * n_tokens


def memory_stats(compiled) -> dict:
    ma = compiled.memory_analysis()
    if ma is None:
        return {}
    return {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        "code_bytes": getattr(ma, "generated_code_size_in_bytes", 0),
    }
