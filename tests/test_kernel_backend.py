"""Kernel backend autodetection (repro.kernels._backend): the one shared
``resolve_interpret`` every ops wrapper consults.

The contract: explicit argument > ``REPRO_PALLAS_INTERPRET`` env > backend
default (TPU compiled, everything else interpret), logged once per backend.
"""

import logging

import jax
import pytest

from repro.kernels import _backend
from repro.kernels._backend import resolve_interpret


@pytest.fixture(autouse=True)
def _clean_backend_state(monkeypatch):
    """Each test sees a fresh announce-set and no env override."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(_backend, "_announced", set())


def test_explicit_argument_wins(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert resolve_interpret(False) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(True) is True


@pytest.mark.parametrize("val,expect", [
    ("1", True), ("0", False), ("false", False), ("False", False),
    ("on", True),
])
def test_env_override(monkeypatch, val, expect):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", val)
    assert resolve_interpret() is expect


def test_backend_defaults(monkeypatch):
    # the real default backend of this process: CPU must interpret (Pallas
    # has no CPU lowering), TPU must compile
    chosen = resolve_interpret()
    if jax.default_backend() == "cpu":
        assert chosen is True
    # forced backend views (resolve_interpret reads jax.default_backend):
    # only the TPU compiles, every other backend interprets
    monkeypatch.setattr(_backend.jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False
    monkeypatch.setattr(_backend.jax, "default_backend", lambda: "gpu")
    assert resolve_interpret() is True


def test_logs_once_per_backend(caplog):
    with caplog.at_level(logging.INFO, logger="repro.kernels"):
        resolve_interpret()
        resolve_interpret()
        resolve_interpret()
    records = [r for r in caplog.records if "Pallas kernels" in r.message]
    assert len(records) == 1


def test_wrappers_route_through_shared_resolver(monkeypatch):
    """The kernel wrappers consult the shared resolver (not a private
    backend probe): forcing interpret via the env is honored end to end."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.pack import gather_rows

    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    seen = {}
    real = _backend.resolve_interpret

    def spy(interpret=None):
        out = real(interpret)
        seen["interpret"] = out
        return out

    import repro.kernels.pack.ops as pack_ops
    monkeypatch.setattr(pack_ops, "resolve_interpret", spy)
    tbl = jnp.arange(12.0, dtype=jnp.float32).reshape(6, 2)
    idx = jnp.asarray([0, 3, 5], jnp.int32)
    out = gather_rows(tbl, idx, impl="kernel")
    assert seen["interpret"] is True
    np.testing.assert_array_equal(np.asarray(out), np.asarray(tbl)[[0, 3, 5]])
