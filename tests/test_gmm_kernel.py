"""The grouped matmul kernel (``repro.kernels.gmm``: the installed JAX's
megablox ``gmm`` behind the repo's wrapper) against its reference,
``jax.lax.ragged_dot``, in interpret mode: groups of uneven sizes, an
empty group, one group holding every row, several m, k and n tiles, in
float32 and bfloat16.

Both accumulate in float32 and round once to the operands' dtype, so they
agree to float32 rounding of the k-term sums (float32) or exactly up to
one bfloat16 ulp of the result where the sums fall near a rounding edge
(bfloat16)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.gmm import gmm, ops
from repro.kernels.gmm.ref import gmm_ref

SIZES = {
    "uneven": [5, 17, 1, 9],
    "empty group": [12, 0, 7, 13],
    "one group holds all": [0, 0, 32, 0],
    "last group only": [0, 0, 0, 32],
}


def _operands(m, k, n, groups, dtype, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (m, k), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (groups, k, n), jnp.float32).astype(dtype)
    return x, w


def _close(got, want, dtype):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = np.abs(want).max()
    # float32: summation order; bfloat16: one ulp of the rounded result
    tol = 1e-5 * scale if dtype == jnp.float32 else 2 ** -7 * scale
    np.testing.assert_allclose(got, want, atol=tol, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_gmm_kernel_matches_ragged_dot(case, dtype):
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    x, w = _operands(int(sizes.sum()), 64, 48, len(SIZES[case]), dtype)
    got = gmm(x, w, sizes, interpret=True)
    assert got.shape == (32, 48) and got.dtype == dtype
    _close(got, gmm_ref(x, w, sizes), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gmm_kernel_over_many_tiles(dtype, monkeypatch):
    # 3 m tiles, 2 k tiles (the float32 scratch accumulates across them)
    # and 2 n tiles; group edges fall inside tiles, one group is empty
    for name in ("TM", "TK", "TN"):
        monkeypatch.setattr(ops, name, 128)
    sizes = jnp.asarray([100, 0, 60, 124, 100], jnp.int32)
    x, w = _operands(384, 256, 256, 5, dtype, seed=1)
    got = gmm(x, w, sizes, interpret=True)
    _close(got, gmm_ref(x, w, sizes), dtype)


def test_gmm_pads_rows_to_its_tile():
    # 37 rows: the wrapper pads to one 128-row tile and drops the padding
    sizes = jnp.asarray([30, 7], jnp.int32)
    x, w = _operands(37, 16, 8, 2, jnp.float32, seed=2)
    got = gmm(x, w, sizes, interpret=True)
    assert got.shape == (37, 8)
    _close(got, gmm_ref(x, w, sizes), jnp.float32)


def test_gmm_serves_its_reference_off_the_tpu():
    # with no mode forced, a CPU process takes ragged_dot, bit for bit
    sizes = jnp.asarray([3, 0, 5], jnp.int32)
    x, w = _operands(8, 16, 8, 3, jnp.float32, seed=3)
    assert jax.default_backend() == "cpu"
    np.testing.assert_array_equal(np.asarray(gmm(x, w, sizes)),
                                  np.asarray(gmm_ref(x, w, sizes)))
