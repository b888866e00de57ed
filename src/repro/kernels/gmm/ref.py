"""Pure-jnp oracle for the grouped matmul kernel: ``jax.lax.ragged_dot``."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def gmm_ref(lhs, rhs, group_sizes):
    """Same contract as the kernel: rows sorted by group, float32
    accumulation, the result in lhs's dtype."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                              preferred_element_type=jnp.float32
                              ).astype(lhs.dtype)
