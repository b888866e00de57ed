"""The one traffic generator: reads a mix's parameters from
``chipbench/traffic/<mix>.json`` and turns them, with the seed, into
requests.

Two loops:

``closed``: ``clients`` callers each keep one request outstanding and send
    the next the moment the last completes, so a slow server gets less
    load.  With more clients than slots a queue is always waiting.
``open``: ``clients`` robots each send one request every ``period_s``
    seconds from a phase of their own, whatever the server does.  Phases
    are stratified: client i starts at (i + u_i) / clients of a period, with
    u_i uniform from the seed, so every seed offers the same rate and the
    same spacing up to the jitter.  Each request is due at its send time
    and has ``deadline_s`` to complete.

Both name the engine's ``slots``, the longest the run waits after the
window for the requests in flight (``drain_s``), and how many supersteps
of fresh chains the check compares (``check_supersteps``).  The first
``pooled_keys`` requests (default 0) take their noise keys from a fixed
pool, in an order drawn from the seed: a closed loop whose window serves
only the chains it starts with then times the same work for every seed.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(mix: str) -> dict:
    spec = json.loads((HERE / "traffic" / f"{mix}.json").read_text())
    if spec["loop"] not in ("closed", "open"):
        raise ValueError(f"{mix}: unknown loop {spec['loop']!r}")
    return spec


def open_schedule(spec: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times (seconds after the window opens) of every request of an
    open loop whose due time falls inside the window, sorted."""
    n, period = int(spec["clients"]), float(spec["period_s"])
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    phase = (np.arange(n) + rng.uniform(size=n)) / n * period
    per_client = int(np.ceil(seconds / period)) + 1
    due = (phase[:, None] + period * np.arange(per_client)[None, :]).ravel()
    return np.sort(due[due < seconds])
