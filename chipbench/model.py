"""A configuration's model family, its seeded weights and the keys drawn
from ``--seed``.

A configuration names its family under ``reference``: the module
``families/<reference>.py``, which holds everything the harness knows of
that architecture (the weights recipe, the plain reference's forward pass,
the FLOPs of a point, what the reference assumes of the program's model).
A new architecture is a new module there, found by that name.

The weights are the family's leaves, named and shaped from the
configuration's model section, drawn on the device in one jitted call
from the configuration's own ``weights.seed``: every run of a cell serves
the same model, and ``--seed`` changes only the requests' noise keys.
Both the served program and the plain reference take their weights from
here, each making its own copy: the reference never reads an array that
the program holds.
"""

from __future__ import annotations

import functools
import importlib.util
import math
import pathlib
import zlib

import jax
import jax.numpy as jnp

FAMILIES = pathlib.Path(__file__).resolve().parent / "families"


def family(cfg: dict):
    """The module of the configuration's family, ``FAMILIES/<reference>.py``."""
    name = cfg["reference"]
    path = FAMILIES / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in FAMILIES.glob("*.py"))
        raise SystemExit(f"{cfg['name']}: no model family {name!r} (no "
                         f"{path}); known families: {known}")
    return _load(str(path))


@functools.lru_cache(maxsize=None)
def _load(path: str):
    """One module object per file, so that a family's jitted functions
    compile once in a process."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_family_{pathlib.Path(path).stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 2**62 (wider than an int32)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def request_base(seed: int) -> jax.Array:
    """The key that every request key of ``seed`` is folded from."""
    return jax.random.fold_in(base_key(seed), 2)


def request_key(seed: int, rid: int) -> jax.Array:
    return jax.random.fold_in(request_base(seed), rid)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return out


def make_weights(cfg: dict, sharding=None):
    """The configuration's weights, made on the device in one jitted call
    from its ``weights.seed``, in the dtype they are served in.

    Each leaf of the family's ``leaf_shapes`` is drawn from its own key,
    the seed's folded with the CRC-32 of its path, at lecun-normal scale
    times ``weights["<path>_scale"]`` where that is given; a leaf whose
    last name is in ``weights["zero"]`` is zero."""
    m, w = cfg["model"], cfg["weights"]
    if w["init"] != "lecun_normal":
        raise ValueError(f"unknown weight init {w['init']!r}")
    dtype = jnp.dtype(m["param_dtype"])
    shapes = family(cfg).leaf_shapes(m)

    def init(key):
        flat = {}
        for path, (shape, fan_in) in shapes.items():
            if path.split("/")[-1] in w["zero"]:
                flat[path] = jnp.zeros(shape, dtype)
                continue
            std = 1.0 / math.sqrt(fan_in)
            if f"{path}_scale" in w:
                std *= w[f"{path}_scale"]
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = (std * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
        return _nest(flat)

    key = jax.random.fold_in(base_key(int(w["seed"])), 1)
    return jax.jit(init, out_shardings=sharding)(key)
