"""Logical-axis -> mesh-axis rules and sharding-tree builders.

Megatron-style tensor parallelism over the mesh `model` axis:
  column-parallel: wq/wk/wv ("heads"->model), w_gate/w_up ("mlp"->model)
  row-parallel:    wo, w_down (same axes; XLA inserts the pair's all-reduce)
  vocab-parallel:  embedding + LM head ("vocab"->model)
  expert-parallel: MoE expert stacks ("experts"->model)
Replicated across `pod` (weights) — the pod axis carries data parallelism;
batch dims shard over ("pod","data").

ZeRO-1: optimizer state additionally shards its largest replicated axis over
`data` (reduces optimizer memory ~data-fold; gather happens in the update).
"""

from __future__ import annotations

import logging
from collections import Counter
from typing import Mapping

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn.param import Boxed, is_boxed, logical_to_pspec

log = logging.getLogger("repro.serving.sharding")

LOGICAL_RULES: dict = {
    "embed": None,
    "embed2": None,
    "mlp": "model",
    "heads": "model",
    "kv_heads": None,  # raw-KV projections stay replicated (n_kv < model axis)
    "head_dim": None,
    "vocab": "model",
    "experts": "model",
    "layers": None,  # scan axis
}

BATCH_AXES = ("pod", "data")


def batch_pspec(mesh: Mesh, *trailing) -> P:
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names)
    return P(axes, *trailing)


def param_pspecs(boxed_tree, mesh: Mesh | None = None,
                 rules: Mapping | None = None, min_shard_elems: int = 65536):
    """Logical axes -> PartitionSpec tree.

    With ``mesh``, specs are *shape-aware*: jax requires sharded dims to
    divide evenly (heads in {4, 8, 24, 25, 40} don't divide a 16-way model
    axis), so non-dividing assignments are dropped and, for large tensors
    left without a model shard, the largest evenly-dividing dim is sharded
    instead (e.g. hymba's 25-head wq shards d_model row-parallel; the extra
    all-reduce is the price of odd head counts on a fixed mesh).
    """
    rules = rules or LOGICAL_RULES

    def fit(box):
        if not is_boxed(box):
            return P()
        spec = logical_to_pspec(box.logical_axes, rules)
        if mesh is None:
            return spec
        shape = box.shape
        entries = list(spec) + [None] * (len(shape) - len(spec))

        def axsize(m):
            return int(np.prod([mesh.shape[a] for a in ((m,) if isinstance(m, str) else m)]))

        used = set()
        for i, m in enumerate(entries):
            if m is None:
                continue
            if shape[i] % axsize(m) != 0 or any(
                a in used for a in ((m,) if isinstance(m, str) else m)
            ):
                entries[i] = None
            else:
                used.update((m,) if isinstance(m, str) else m)
        total = int(np.prod(shape)) if shape else 0
        if (
            "model" in mesh.axis_names
            and "model" not in used
            and total >= min_shard_elems
        ):
            size = mesh.shape["model"]
            cands = [
                i
                for i, (ax, dim) in enumerate(zip(box.logical_axes, shape))
                if entries[i] is None and ax != "layers"
                and dim % size == 0 and dim >= size
            ]
            if cands:
                best = max(cands, key=lambda i: shape[i])
                entries[best] = "model"
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree_util.tree_map(fit, boxed_tree, is_leaf=is_boxed)


def slots_mesh(num_shards: int, devices=None) -> Mesh:
    """1-D mesh over the shard devices, axis name ``"slots"`` — the serving
    topology axis.  Each device of the mesh hosts exactly one shard's slot
    sub-batch; ``shard_map`` over this axis is how the packed superstep runs
    every shard in ONE dispatch with shard-LOCAL pack maps (see
    ``repro.serving.packing.round.sharded_packed_superstep``).  On CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` simulates the
    devices."""
    devs = list(dict.fromkeys(  # ordered dedupe: placements may wrap
        devices if devices is not None else jax.devices()))
    if len(devs) < num_shards:
        raise ValueError(
            f"slots_mesh needs {num_shards} distinct devices, have "
            f"{len(devs)} "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return Mesh(np.asarray(devs[:num_shards]), ("slots",))


def serving_mesh(num_shards: int, model_parallel: int = 1, devices=None) -> Mesh:
    """2-D serving topology: ``(slots, model)``.  Row i is shard i's
    model-parallel device GROUP — the slot sub-batch is replicated across the
    row while the verify weights shard over it (``tp_param_pspecs``), so the
    packed superstep still runs every shard in ONE dispatch per boundary with
    the tensor-parallel all-reduces INSIDE the ``shard_map`` program.  With
    ``model_parallel=1`` this degenerates to ``slots_mesh`` plus a trivial
    model axis; the engine keeps using ``slots_mesh`` there so the mp=1
    executables stay bit-identical to the replicated path."""
    n = num_shards * model_parallel
    devs = list(dict.fromkeys(  # ordered dedupe: placements may wrap
        devices if devices is not None else jax.devices()))
    if len(devs) < n:
        raise ValueError(
            f"serving_mesh needs {num_shards}x{model_parallel}={n} distinct "
            f"devices, have {len(devs)} "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count)")
    grid = np.asarray(devs[:n]).reshape(num_shards, model_parallel)
    return Mesh(grid, ("slots", "model"))


def model_group_placements(num_shards: int, model_parallel: int,
                           devices=None) -> list[list]:
    """Per-worker device GROUPS for per-shard-dispatch model parallelism:
    shard i owns ``devices[i*mp:(i+1)*mp]`` — the same row-major grouping as
    ``serving_mesh`` rows, so fused and per-shard dispatch place identical
    weights shards on identical devices."""
    n = num_shards * model_parallel
    devs = list(dict.fromkeys(devices if devices is not None else jax.devices()))
    if len(devs) < n:
        raise ValueError(
            f"model_group_placements needs {n} distinct devices, have "
            f"{len(devs)} "
            "(on CPU set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return [devs[i * model_parallel:(i + 1) * model_parallel]
            for i in range(num_shards)]


# Manual-TP whitelist: the (layers-stripped) logical signatures the TP-aware
# serving forward (attention wq/wo/bq head slicing, ffn w_gate/w_up/w_down
# hidden slicing + their psums) knows how to compute on.  Everything else —
# kv projections, embeddings, norms, MoE/ssm stacks — stays replicated, because
# under manual shard_map there is no compiler to insert the matching
# collective for an arbitrary sharded dim.
TP_VERIFY_SIGS = frozenset({
    ("embed", "heads", "head_dim"),   # wq (column-parallel)
    ("heads", "head_dim", "embed"),   # wo (row-parallel; forward psums after)
    ("heads", "head_dim"),            # bq
    ("embed", "mlp"),                 # w_gate / w_up (column-parallel)
    ("mlp", "embed"),                 # w_down (row-parallel; forward psums)
})

# Expert-parallel whitelist: the MoE expert stacks the EP-aware dispatch
# (repro.nn.moe: local-expert gather + all_to_all token exchange + psum
# combine) computes on.  Each device owns E/mp expert FFNs; the router stays
# replicated (it routes every token on every rank).
EP_VERIFY_SIGS = frozenset({
    ("experts", "embed", "mlp"),      # w_gate / w_up expert stacks
    ("experts", "mlp", "embed"),      # w_down expert stack
})

# one-time replication warnings (satellite: misconfigured mp must be visible)
_REPLICATION_WARNED: set = set()


def _warn_replicated(leaf_name: str, core_sig: tuple, axis_name: str,
                     dim: int, size: int) -> None:
    key = (leaf_name, core_sig, dim, size)
    if key in _REPLICATION_WARNED:
        return
    _REPLICATION_WARNED.add(key)
    log.warning(
        "model-parallel layout: leaf %r (logical %s) replicates on every "
        "device — its %r dim (%d) does not divide the %d-way model axis; "
        "the verify serves it unsharded (no memory win for this leaf)",
        leaf_name, "/".join(core_sig), axis_name, dim, size)


def mp_param_pspecs(boxed_tree, mesh: Mesh, *, tensor: bool = True,
                    expert: bool = False):
    """Model-parallel serving layout over the mesh ``model`` axis.

    Unlike ``param_pspecs`` (whose compiler-assisted layout may shard ANY
    evenly-dividing dim and rely on XLA to insert collectives), this shards
    ONLY the axes the manual-SPMD serving forward explicitly exchanges for:

      tensor  head/hidden axes of ``TP_VERIFY_SIGS`` (attention + dense FFN
              slice locally and psum in-program);
      expert  the leading ``experts`` axis of ``EP_VERIFY_SIGS`` (the MoE
              dispatch gathers locally, all_to_all-exchanges tokens, and
              psum-combines — each device owns E/mp expert stacks).

    Shape-aware like ``param_pspecs``: a whitelisted leaf whose axis doesn't
    divide the model-axis size falls back to replication (odd head/expert
    counts serve replicated rather than erroring; the verify then simply
    skips its slice/exchange) — with a one-time ``repro.serving`` WARNING
    naming the leaf and the axis size, so mp misconfiguration is visible."""
    size = int(mesh.shape["model"])

    def fit(path, box):
        if not is_boxed(box):
            return P()
        name = next((str(getattr(p, "key", p)) for p in reversed(path)
                     if getattr(p, "key", None) is not None), "?")
        axes = tuple(box.logical_axes)
        core = tuple(a for a in axes if a != "layers")
        is_tp = tensor and core in TP_VERIFY_SIGS
        is_ep = expert and core in EP_VERIFY_SIGS
        if size <= 1 or not (is_tp or is_ep):
            return P()
        shard_axes = ("experts",) if is_ep else ("heads", "mlp")
        entries = []
        for a, dim in zip(axes, box.shape):
            if a in shard_axes and dim % size == 0 and dim >= size:
                entries.append("model")
            else:
                entries.append(None)
        if "model" not in entries:
            # non-dividing: replicate the whole leaf (and say so, once)
            bad_ax, bad_dim = next(
                ((a, d) for a, d in zip(axes, box.shape) if a in shard_axes),
                ("?", 0))
            _warn_replicated(name, core, bad_ax, int(bad_dim), size)
            return P()
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree_util.tree_map_with_path(fit, boxed_tree, is_leaf=is_boxed)


def tp_param_pspecs(boxed_tree, mesh: Mesh):
    """PR 7 entry point: tensor-parallel-only layout (experts replicated).
    Kept as the stable name; ``mp_param_pspecs`` generalizes it with the
    expert-parallel whitelist."""
    return mp_param_pspecs(boxed_tree, mesh, tensor=True, expert=False)


def measure_collective_seconds(mesh: Mesh, payload_bytes, axis: str = "model",
                               repeats: int = 3,
                               kind: str = "psum") -> float:
    """Measured wall seconds for ONE round's worth of model-parallel
    collectives on this mesh: a jitted ``shard_map`` program runs one
    collective per payload over ``axis`` (same op, same axis, same devices
    as the verify's in-program collectives), timed best-of-``repeats`` after
    a warmup.  This is the calibration behind ``EngineStats.collective_s`` —
    the superstep's collectives run inside one fused program, so their cost
    cannot be timed in isolation in situ; the probe re-creates the payload
    schedule outside and the engine attributes ``probe x rounds`` per
    dispatch.

    Payloads of one size run back to back on ONE buffer (a loop whose
    every step depends on the last, as the layers of the verify do), so the
    probe holds one buffer per distinct size, not the whole schedule: a
    4-slot tensor-parallel round of ``paper-ldm-dit`` is 56 psums of 85 MB,
    and buffers for all of them would take 9.5 GB of each chip.

    ``kind`` selects the probed collective: ``"psum"`` (tensor-parallel
    all-reduces, and the EP/SP output combines) or ``"all_to_all"`` (the
    EP token exchange and the Ulysses sequence<->head trades).  The two are
    calibrated SEPARATELY — an all-reduce moves (world-1)/world of the
    buffer twice per device while an all-to-all moves (world-1)/world once,
    so one probe cannot price both."""
    import time as _time

    if kind not in ("psum", "all_to_all"):
        raise ValueError(f"unknown collective kind {kind!r}")
    # elements per payload -> how many collectives of that size
    counts = Counter(max(int(b) // 4, 1) for b in payload_bytes)
    if not counts or axis not in mesh.axis_names or mesh.shape[axis] <= 1:
        return 0.0
    world = int(mesh.shape[axis])

    if kind == "psum":
        def op(x):
            return jax.lax.psum(x, axis)
        shapes = [(n,) for n in counts]
    else:
        # a (world, n/world) buffer keeps its shape under the tiled
        # all_to_all while every element still crosses the axis
        def op(x):
            return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)
        shapes = [(world, max(n // world, 1)) for n in counts]

    def body(*xs):
        return tuple(jax.lax.fori_loop(0, c, lambda _, x: op(x), x)
                     for x, c in zip(xs, counts.values()))

    rep = P()
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(rep,) * len(shapes),
        out_specs=(rep,) * len(shapes), check_vma=False))
    xs = tuple(jax.device_put(np.zeros(s, np.float32),
                              NamedSharding(mesh, P())) for s in shapes)
    jax.block_until_ready(fn(*xs))  # compile + warm
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*xs))
        best = min(best, _time.perf_counter() - t0)
    return best


def measure_collective_seconds_by_kind(mesh: Mesh, payloads_by_kind,
                                       axis: str = "model",
                                       repeats: int = 3) -> dict:
    """Per-kind calibration: ``{"psum": [...bytes...], "all_to_all": [...]}``
    -> ``{"psum": seconds, "all_to_all": seconds}`` (kinds with an empty
    payload schedule are omitted).  The engine sums these for the legacy
    ``collective_s`` total and reports each lane separately so
    ``timing_breakdown()`` doesn't misattribute EP/SP exchange time to the
    TP all-reduces."""
    out = {}
    for kind, payloads in dict(payloads_by_kind).items():
        payloads = [int(b) for b in payloads if int(b) > 0]
        if not payloads:
            continue
        out[kind] = measure_collective_seconds(
            mesh, payloads, axis=axis, repeats=repeats, kind=kind)
    return out


def shard_pspecs(mesh: Mesh, states=None, axis: str = "slots"):
    """Stacked-shard layout: every leaf of a (num_shards, slots_local, ...)
    slot batch shards its leading SHARD axis over the mesh ``slots`` axis —
    one shard's sub-batch per device, slot and event dims local.  The
    topology contract of sharded serving: any gather/scatter built from
    shard-local pack maps then stays device-local by construction.

    With ``states`` returns a matching pytree of shardings; without, the
    single ``NamedSharding`` (device_put broadcasts it over a pytree)."""
    sh = NamedSharding(mesh, P(axis))
    if states is None:
        return sh
    return jax.tree_util.tree_map(lambda _: sh, states)


def shard_placements(num_shards: int, devices=None) -> list:
    """Per-worker device list for the per-shard-dispatch serving path: shard
    i's slot batch, allocator weights, and superstep dispatches are pinned
    to ``devices[i % len(devices)]``.  With fewer devices than shards the
    assignment wraps (shards co-locate); with one device everything lands
    there — the degenerate single-host layout."""
    devs = list(devices if devices is not None else jax.devices())
    return [devs[i % len(devs)] for i in range(num_shards)]


def chain_state_shardings(mesh: Mesh, states=None):
    """Slot-batch layout for the continuous serving engine: every leaf of a
    vmapped ``ASDChainState`` (leading axis = slots) shards that axis over
    the batch axes ("pod","data"); per-slot scalars and trailing event dims
    stay unsharded.  The (slots x theta)-point verification forward inside
    ``asd_round`` then runs data-parallel across the mesh.

    With ``states`` returns a matching pytree of shardings; without, the
    single ``NamedSharding`` (device_put broadcasts it over a pytree)."""
    sh = NamedSharding(mesh, batch_pspec(mesh))
    if states is None:
        return sh
    return jax.tree_util.tree_map(lambda _: sh, states)


def shardings_from_pspecs(mesh: Mesh, pspec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def zero1_pspec(spec: P, shape, mesh: Mesh) -> P:
    """Add `data` sharding on the first large axis a param leaves replicated.

    This is ZeRO-1 for the AdamW mu/nu tensors: each data-parallel rank owns a
    slice of optimizer state.  Falls back to the original spec when no axis
    divides evenly.
    """
    if "data" not in mesh.axis_names:
        return spec
    dsize = mesh.shape["data"]
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % dsize == 0 and dim >= dsize:
            entries[i] = "data"
            return P(*entries)
    return spec


def fsdp_pspecs(boxed_tree, mesh: Mesh, min_shard_elems: int = 65536):
    """ZeRO-3/FSDP layout: every large param shards its largest evenly-
    dividing dim over the flattened ("data","model") axis pair (the whole
    mesh acts as one DP world; XLA all-gathers weights at use and
    reduce-scatters grads).  Collective volume is O(params), independent of
    tokens — the right regime when TP activation all-reduces dominate
    (see EXPERIMENTS.md §Perf, dbrx-132b train_4k)."""
    axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    world = int(np.prod([mesh.shape[a] for a in axes]))

    def fit(box):
        if not is_boxed(box):
            return P()
        shape = box.shape
        if int(np.prod(shape)) < min_shard_elems:
            return P()
        cands = [
            i for i, (ax, dim) in enumerate(zip(box.logical_axes, shape))
            if ax != "layers" and dim % world == 0 and dim >= world
        ]
        if not cands:
            # fall back to model-axis-only sharding
            m = mesh.shape["model"]
            cands = [
                i for i, (ax, dim) in enumerate(zip(box.logical_axes, shape))
                if ax != "layers" and dim % m == 0 and dim >= m
            ]
            if not cands:
                return P()
            best = max(cands, key=lambda i: shape[i])
            entries = [None] * len(shape)
            entries[best] = "model"
            return P(*entries)
        best = max(cands, key=lambda i: shape[i])
        entries = [None] * len(shape)
        entries[best] = axes
        return P(*entries)

    return jax.tree_util.tree_map(fit, boxed_tree, is_leaf=is_boxed)


def replicated_pspecs(boxed_tree):
    """DP-serve layout: weights fully replicated (small denoisers)."""
    return jax.tree_util.tree_map(
        lambda b: P(), boxed_tree, is_leaf=is_boxed
    )


def opt_state_pspecs(param_pspec_tree, param_shapes, mesh: Mesh, zero1: bool = True):
    """mu/nu mirror params (optionally ZeRO-1 sharded); step is replicated."""

    def one(spec, shape):
        return zero1_pspec(spec, shape.shape, mesh) if zero1 else spec

    mu = jax.tree_util.tree_map(
        one, param_pspec_tree, param_shapes,
        is_leaf=lambda x: isinstance(x, P),
    )
    return {"mu": mu, "nu": mu, "step": P()}


def abstract_params(init_fn, key):
    """Shape-only init (no allocation): eval_shape through the boxed tree."""
    return jax.eval_shape(init_fn, key)
