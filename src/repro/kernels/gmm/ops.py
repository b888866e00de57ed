"""Public wrapper of the grouped matmul: row padding, tile choice, and the
backend's choice between the installed JAX's megablox kernel and its
reference.

The kernel is ``jax.experimental.pallas.ops.tpu.megablox``'s ``gmm``: it
visits each (m tile, group) pair that holds rows, so a tile that two groups
share is computed once per group and a group with no rows costs nothing,
and it stores only the rows of the visiting group.  It appears in the
device trace as ``gmm``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm

from repro.kernels._backend import resolve_interpret
from repro.kernels._padding import LANE, pad_dim
from repro.kernels.gmm.ref import gmm_ref

TM, TK, TN = 512, 1152, 1152  # largest tiles tried; each dim takes a divisor


def _tile(dim: int, most: int) -> int:
    """The largest multiple of the lane width that divides ``dim`` and is at
    most ``most``; the whole of a dim that is no multiple of it."""
    if dim % LANE:
        return dim
    return max(t for t in range(LANE, min(dim, most) + 1, LANE)
               if dim % t == 0)


def gmm(lhs, rhs, group_sizes, interpret: bool | None = None):
    """``out[r] = lhs[r] @ rhs[g]`` for the ``group_sizes[g]`` rows of each
    group ``g``, the rows of lhs (m, k) sorted by group, rhs (G, k, n);
    the sizes sum to m.  Returns (m, n) in lhs's dtype, accumulated in
    float32.

    Where the kernels would run in interpret mode (``resolve_interpret``:
    any backend but the TPU) the reference serves; an explicit
    ``interpret`` runs the kernel in that mode.
    """
    if interpret is None and resolve_interpret():
        return gmm_ref(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    tm = min(TM, -(-m // LANE) * LANE)
    pad = -m % tm
    # the kernel takes whole m tiles: the padding rows join the last group,
    # so that every row of the output is written, and are dropped
    sizes = group_sizes.astype(jnp.int32).at[-1].add(pad)
    out = megablox_gmm(pad_dim(lhs, pad, axis=0), rhs, sizes,
                       preferred_element_type=lhs.dtype,
                       tiling=(tm, _tile(lhs.shape[1], TK),
                               _tile(rhs.shape[2], TN)),
                       interpret=resolve_interpret(interpret))
    return out[:m]
