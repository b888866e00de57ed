"""int8 gradient compression: quantization error bounds + the multi-device
psum path (subprocess with 8 host devices)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.compression import qdq, quantize_int8, dequantize_int8


def test_qdq_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (1024,)) * 3.0
    y = qdq(x)
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(y - x))) <= amax / 127.0 * 0.51


def test_qdq_zero_and_sign():
    x = jnp.asarray([0.0, -1.0, 1.0])
    y = qdq(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=1e-6)


def test_stochastic_rounding_unbiased():
    x = jnp.full((20000,), 0.3)
    q, s = quantize_int8(x, key=jax.random.PRNGKey(1))
    y = dequantize_int8(q, s)
    assert abs(float(y.mean()) - 0.3) < 5e-3


PSUM_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.distributed.compression import int8_psum_tree

    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2, 2), ("pod", "data", "model"))
    fn = jax.shard_map(
        lambda g: int8_psum_tree(g, "pod"),
        mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
        check_vma=False, axis_names={"pod"},
    )
    x = jnp.arange(16, dtype=jnp.float32).reshape(2, 8)
    y = np.asarray(jax.jit(fn)(x))
    expect = np.tile((np.arange(8) + np.arange(8, 16)) / 2.0, (2, 1))
    err = np.abs(y - expect).max()
    assert err <= 15.0 / 127.0, err  # one quantization step at this amax
    print("OK", err)
    """
)


@pytest.mark.slow
def test_int8_psum_multi_device_subprocess():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run(
        [sys.executable, "-c", PSUM_SCRIPT], capture_output=True, text=True,
        env=env, cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.startswith("OK")
