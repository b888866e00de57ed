"""Shared Pallas backend resolution for every kernel wrapper.

One question, answered once: should a ``pallas_call`` run compiled or in
interpret mode on THIS process's default backend?

Resolution order:

  1. ``REPRO_PALLAS_INTERPRET=0/1`` env override — forced compiled / forced
     interpret, whatever the backend (the escape hatch for debugging a
     kernel on TPU).
  2. TPU: compiled (the Mosaic lowering is the native target).
  3. CPU (and anything else): interpret — Pallas has no CPU lowering.

The chosen path is logged ONCE per process per backend, so a serving log
always shows which lane the kernels took.
"""

from __future__ import annotations

import logging
import os

import jax

_log = logging.getLogger("repro.kernels")

# backends already logged: the decision is per-backend, the log is once-only
_announced: set = set()


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The interpret flag a kernel wrapper should pass to ``pallas_call``.

    An explicit ``interpret`` argument wins (callers forcing a mode, e.g.
    parity tests running both lanes).  Otherwise the env override and the
    backend decide, and the decision is logged once.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    backend = jax.default_backend()
    if env is not None and env != "":
        chosen = env not in ("0", "false", "False")
        reason = f"REPRO_PALLAS_INTERPRET={env}"
    elif backend == "tpu":
        chosen, reason = False, "TPU Mosaic lowering"
    else:
        chosen, reason = True, f"{backend} has no Pallas lowering"
    if backend not in _announced:
        _announced.add(backend)
        _log.info(
            "Pallas kernels on backend %r: %s (%s)",
            backend, "interpret mode" if chosen else "compiled", reason)
    return chosen
