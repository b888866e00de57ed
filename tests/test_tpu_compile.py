"""The serving path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other test here) cannot see what Mosaic refuses:
unsupported vector types, tiles that overflow the scoped VMEM.  These tests
lower each kernel through its public wrapper for one chip of a described
``v5e:2x2`` topology and compile it with the installed TPU compiler — no
chip is attached, nothing runs.  Widths are those of ``paper-ldm-dit``:
a 1024 x 16 latent event, 16 slots x theta 32 = 512 window rows, and
attention over 1024 tokens with 16 heads of 72; the grouped matmul at
``paper-dit-moe-xl``'s expert widths.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.  The persistent compilation cache is off around these compiles — an
entry compiled for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.ops import flash_mha
from repro.kernels.gmm import gmm
from repro.kernels.grs.ops import grs
from repro.kernels.pack import gather_rows, scatter_rows
from repro.kernels.superstep import fused_gather, fused_verify_commit

EVENT = (1024, 16)  # paper-ldm-dit latent: tokens x channels
ROWS = 512  # window rows: 16 slots x theta 32
HEADS, HEAD_DIM, SEQ = 16, 72, 1024
# paper-dit-moe-xl's expert products: 16 slots x 9 points x 256 tokens x
# top-2 = 73,728 routed rows, 8 experts of 1152 -> 4608 -> 1152
MOE_ROWS, EXPERTS, D_MODEL, D_FF = 73728, 8, 1152, 4608


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is in there
    return compiled


def _arg(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_grs_compiles(one_chip):
    rows = lambda: _arg(one_chip, (ROWS,) + EVENT)  # noqa: E731
    _compile(lambda u, xi, mh, m, s: grs(u, xi, mh, m, s, event_ndim=2,
                                         interpret=False),
             _arg(one_chip, (ROWS,)), rows(), rows(), rows(),
             _arg(one_chip, (ROWS,)))


def test_gather_rows_compiles(one_chip):
    _compile(lambda src, idx: gather_rows(src, idx, interpret=False),
             _arg(one_chip, (ROWS,) + EVENT),
             _arg(one_chip, (ROWS,), jnp.int32))


def test_scatter_rows_compiles(one_chip):
    _compile(lambda v, idx: scatter_rows(v, idx, ROWS, interpret=False),
             _arg(one_chip, (ROWS,) + EVENT),
             _arg(one_chip, (ROWS,), jnp.int32))


def test_fused_gather_compiles(one_chip):
    tbl = lambda: _arg(one_chip, (ROWS,) + EVENT)  # noqa: E731
    _compile(lambda y, xi, mh, sc, idx: fused_gather(
                 y, xi, mh, sc, idx, impl="kernel", interpret=False),
             tbl(), tbl(), tbl(), _arg(one_chip, (ROWS, 5)),
             _arg(one_chip, (ROWS,), jnp.int32))


def test_fused_verify_commit_compiles(one_chip):
    pts = lambda: _arg(one_chip, (ROWS,) + EVENT)  # noqa: E731
    lane = lambda: _arg(one_chip, (ROWS,))  # noqa: E731
    _compile(lambda y, g, xi, mh, A, B, u, s, idx: fused_verify_commit(
                 y, g, xi, mh, A, B, u, s, idx, ROWS, impl="kernel",
                 interpret=False),
             pts(), pts(), pts(), pts(), lane(), lane(), lane(), lane(),
             _arg(one_chip, (ROWS,), jnp.int32))


def test_flash_mha_compiles(one_chip):
    qkv = lambda: _arg(one_chip, (8, SEQ, HEADS, HEAD_DIM),  # noqa: E731
                       jnp.bfloat16)
    _compile(lambda q, k, v: flash_mha(q, k, v, causal=False,
                                       interpret=False),
             qkv(), qkv(), qkv())


@pytest.mark.parametrize("k, n", [(D_MODEL, D_FF), (D_FF, D_MODEL)])
def test_gmm_compiles(one_chip, k, n):
    _compile(lambda x, w, s: gmm(x, w, s, interpret=False),
             _arg(one_chip, (MOE_ROWS, k), jnp.bfloat16),
             _arg(one_chip, (EXPERTS, k, n), jnp.bfloat16),
             _arg(one_chip, (EXPERTS,), jnp.int32))
