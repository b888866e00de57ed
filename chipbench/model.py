"""A configuration's seeded weights and the keys drawn from ``--seed``.

The weights are the registry transformer's leaves, named and shaped as the
configuration file states them, drawn on the device in one jitted call
from the configuration's own ``weights.seed``: every run of a cell serves
the same model, and ``--seed`` changes only the requests' noise keys.
Both the served program and the plain reference take their weights from
here, each making its own copy: the reference never reads an array that
the program holds.
"""

from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any seed up to 2**62 (wider than an int32)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF)


def request_key(seed: int, rid: int) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(base_key(seed), 2), rid)


def leaf_shapes(m: dict) -> dict:
    """{path: (shape, fan_in)} of the denoiser's weights; layers stacked."""
    n, d, ff = m["n_layers"], m["d_model"], m["d_ff"]
    h, kv, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    C, td = m["d_data"], m["time_dim"]
    return {
        "in_proj": ((C, d), C),
        "t_mlp1": ((td, d), td),
        "t_mlp2": ((d, d), d),
        "decoder/g0/attn_norm/scale": ((n, d), 0),
        "decoder/g0/attn/wq": ((n, d, h, hd), d),
        "decoder/g0/attn/wk": ((n, d, kv, hd), d),
        "decoder/g0/attn/wv": ((n, d, kv, hd), d),
        "decoder/g0/attn/wo": ((n, h, hd, d), h * hd),
        "decoder/g0/ffn_norm/scale": ((n, d), 0),
        "decoder/g0/ffn/w_gate": ((n, d, ff), d),
        "decoder/g0/ffn/w_up": ((n, d, ff), d),
        "decoder/g0/ffn/w_down": ((n, ff, d), ff),
        "final_norm/scale": ((d,), 0),
        "out_proj": ((d, C), d),
    }


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
    from its ``weights.seed``, in the dtype they are served in."""
    m, w = cfg["model"], cfg["weights"]
    if w["init"] != "lecun_normal":
        raise ValueError(f"unknown weight init {w['init']!r}")
    dtype = jnp.dtype(m["param_dtype"])
    shapes = leaf_shapes(m)

    def init(key):
        flat = {}
        for path, (shape, fan_in) in shapes.items():
            if path.split("/")[-1] in w["zero"]:
                flat[path] = jnp.zeros(shape, dtype)
                continue
            std = 1.0 / math.sqrt(fan_in)
            if path == "out_proj":
                std *= w["out_proj_scale"]
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = (std * jax.random.normal(k, shape, jnp.float32)
                          ).astype(dtype)
        return _nest(flat)

    key = jax.random.fold_in(base_key(int(w["seed"])), 1)
    return jax.jit(init, out_shardings=sharding)(key)
