"""Sharded continuous-batching ASD serving: shard-local workers behind a
request router, with per-shard admission queues and budget rebalancing.

Topology::

            submit(request)
                  |
               Router          (round-robin / least-loaded / deadline —
                  |             repro.serving.router; host-side only)
        +---------+---------+
        |         |         |
    ShardWorker ShardWorker ShardWorker      repro.serving.worker
     queue 0     queue 1     queue 2         per-shard SlotScheduler
     slots 0     slots 1     slots 2         per-shard ASDChainState batch
     budget 0    budget 1    budget 2        per-shard round_budget tier
        |         |         |
     device 0  device 1  device 2            shard_placements(...)

Every worker is a self-contained shard: its packed rounds gather
verification points only across ITS OWN slots (pack maps are shard-local by
construction — no cross-shard, and on a real mesh no cross-host, gathers),
and its admission queue defers or drops under ITS OWN budget pressure.

Two dispatch shapes drive the shards:

  ``dispatch="per-shard"``   each worker launches its own superstep program
      (a boundary dispatches all shards back-to-back before harvesting
      any); shards may run DIFFERENT budget tiers and superstep lengths and
      live on any device layout.
  ``dispatch="fused"``       every shard's superstep runs in ONE
      ``shard_map`` program over a ``slots``-sharded mesh (one device per
      shard): the slot state lives stacked and sharded, XLA executes the
      per-shard programs concurrently across devices, and the boundary
      costs ONE dispatch + ONE sync however many shards there are — the
      shape that scales on a pod and under CPU multi-device simulation.
      Requires a common rounds_per_sync across shards; budgets must be
      common too unless ``round_impl="fused"`` (budget-as-data), where
      per-shard tiers ride into the one program as a sharded vector.

Exactness: routing and sharding are pure host-side scheduling.  A chain's
trajectory depends only on its own ``ASDChainState`` (per-request key), so a
key-carrying request serves the SAME bits whatever shard it lands on —
``ShardedASDEngine(shards=1)`` is bit-identical to ``ContinuousASDEngine``
(same worker core, same loop), and shards=2/4 reproduce the single-shard
samples per request whenever grants equal demands (unpacked execution, or
packed at covering budgets; a BINDING budget couples a chain's effective
windows to its co-resident chains, which shard placement changes).

Budget rebalancing: each worker re-picks its ``round_budget`` at superstep
boundaries from its own live-demand EWMA on a power-of-two ladder with
hysteresis (``round_budget="auto"`` — see ``ShardWorker._pick_budget``), so
a shard whose chains are closing their windows hands compute back without
any cross-shard coordination; executables are shared across shards from one
per-(R, budget) cache.
"""

from __future__ import annotations

import logging
import time
from typing import List, Optional

log = logging.getLogger("repro.serving.sharded")

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.metrics import EngineStats
from repro.serving.obs.trace import span
from repro.serving.router import LeastLoaded, Router
from repro.serving.worker import (
    _SYNC_ROWS,
    Lane,
    Request,
    ShardWorker,
    dispatch_admissions,
    step_lanes,
)

__all__ = ["ShardedASDEngine"]

# ring lanes of the fused front end's spans (pid = num_shards); "route"
# instants take lane 2; a compile takes the lane of the dispatch it stands
# in for
_FRONTEND_LANES = {"fused_dispatch": 0, "compile": 0,
                   "fused_device_wait": 1, "harvest": 1, "admit": 3,
                   "step": 4}


class ShardedASDEngine:
    """N shard-local ``ShardWorker``s behind a pluggable ``Router``.

    Arguments mirror ``ContinuousASDEngine`` (they are forwarded to every
    worker) plus the sharding front end:

      shards: number of shard-local workers.  ``num_slots`` is the TOTAL
        slot count and must divide evenly (each worker gets
        ``num_slots // shards`` lanes).
      model_shards: tensor parallelism WITHIN each shard — every shard owns
        an ``mp``-device model group (a ``serving_mesh`` row) and its verify
        call runs tensor-parallel over the group's ``"model"`` axis (QKV /
        output projections and FFN sharded per ``tp_param_pspecs``, the
        all-reduce inside the program).  Needs ``shards * model_shards``
        devices, explicit ``params`` + ``param_specs``, and a
        ``model_fn_factory`` built with ``tp_axis="model"``.  ``1``
        (default) keeps every existing code path bit-identical.
      router: ``repro.serving.router.Router`` picking the shard a submitted
        request joins (default: least-loaded).
      dispatch: ``"per-shard"`` (default) launches each worker's superstep
        as its own device program — shards may run different budget tiers
        and superstep lengths, and live on any device layout.
        ``"fused"`` runs EVERY shard's superstep in ONE ``shard_map``
        dispatch over a ``slots``-sharded mesh (one device per shard,
        needs ``len(devices) >= shards``): the slot state lives stacked
        (shards, slots_local, ...) and XLA executes the per-shard programs
        concurrently across devices — the dispatch shape that actually
        scales on a pod (and on CPU multi-device simulation), at the cost
        of one common rounds_per_sync across shards.  A common round_budget
        is required too UNLESS ``round_impl="fused"``: budget-as-data keeps
        the pack shape at the static cap, so per-shard auto tiers travel as
        a sharded data vector and ``round_budget="auto"`` composes with
        fused dispatch.  Both modes run the identical per-shard math —
        bit-identical samples (asserted in tests).
      devices: optional explicit per-shard device list (e.g. from
        ``repro.distributed.sharding.shard_placements``).  Default: with
        multiple shards and multiple local devices, shard i is pinned to
        device i (round-robin); single-shard engines stay unpinned so
        ``shards=1`` is bit-identical to ``ContinuousASDEngine``.
      round_budget: PER-SHARD verification budget (packed execution): each
        shard's round is one budget-shaped model call over its own slots.
        ``"auto"`` turns on per-shard tier rebalancing.
      seed: worker i derives its PRNG stream from ``seed + 1000003 * i`` (so
        shard 0 matches the single-shard engine bit for bit); requests that
        carry their own key are unaffected.

    Compiled programs are shared: workers 1.. adopt worker 0's
    per-(R, budget) executable cache, so N shards with identical shapes
    compile once.
    """

    def __init__(
        self,
        model_fn_factory,
        schedule,
        event_shape,
        num_slots: int = 8,
        *,
        shards: int = 1,
        model_shards: int = 1,
        router: Optional[Router] = None,
        dispatch: str = "per-shard",
        devices: Optional[list] = None,
        seed: int = 0,
        **worker_kwargs,
    ):
        # model_shards (mp): model parallelism WITHIN each shard — one
        # shard = an mp-device model group (serving_mesh row).  mp=1 keeps
        # every existing code path bit-identical.  mp>1 needs explicit
        # ``params`` plus ``param_specs`` (a tp_param_pspecs or
        # mp_param_pspecs tree — tensor- and/or expert-parallel; Ulysses
        # sequence parallelism rides the same axis with replicated weights)
        # in worker_kwargs, a model_fn_factory built with
        # tp_axis/ep_axis/sp_axis="model", and shards*mp distinct devices;
        # ``collective_payloads`` (a {kind: [bytes...]} dict from
        # mp_collective_payloads, or a legacy flat psum list) calibrates
        # EngineStats.collective_s and its per-kind split.
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards}")
        if model_shards < 1:
            raise ValueError(f"model_shards must be >= 1, got {model_shards}")
        if num_slots % shards:
            raise ValueError(
                f"num_slots {num_slots} must divide evenly over {shards} "
                f"shards (each worker owns an equal slot sub-batch)")
        if dispatch not in ("per-shard", "fused"):
            raise ValueError(f"unknown dispatch mode {dispatch!r}")
        self.num_shards = shards
        self.num_slots = num_slots
        self.model_shards = int(model_shards)
        self.dispatch = dispatch
        slots_local = num_slots // shards
        self.router = router if router is not None else LeastLoaded()
        fused = dispatch == "fused"
        mp = self.model_shards
        # engine-level TP inputs: the spec tree shards weights over the
        # "model" axis, the payload schedule calibrates collective_s
        param_specs = worker_kwargs.pop("param_specs", None)
        collective_payloads = worker_kwargs.pop("collective_payloads", ())
        if mp > 1 and (worker_kwargs.get("params") is None
                       or param_specs is None):
            raise ValueError(
                "model_shards > 1 needs explicit params AND param_specs "
                "(tp_param_pspecs tree): a factory closure cannot be "
                "sharded over a model group")
        self._param_specs = param_specs if mp > 1 else None
        self._collective_payloads = (
            dict(collective_payloads) if isinstance(collective_payloads, dict)
            else tuple(collective_payloads))
        if (fused and worker_kwargs.get("round_budget") == "auto"
                and worker_kwargs.get("round_impl") != "fused"):
            raise ValueError(
                'round_budget="auto" (per-shard budget tiers) requires '
                'dispatch="per-shard": one fused shard_map program cannot '
                "give shards different static budgets.  Use "
                'round_impl="fused" (budget-as-data) to carry per-shard '
                "tiers as data inside one fused program.")
        if devices is None and shards > 1 and not fused and mp == 1:
            local = jax.devices()
            if len(local) > 1:
                devices = [local[i % len(local)] for i in range(shards)]
        if devices is not None and len(devices) < shards * mp:
            raise ValueError(
                f"devices list ({len(devices)}) shorter than shards x "
                f"model_shards ({shards} x {mp})")
        groups = None
        if mp > 1 and not fused:
            # per-shard TP: shard i's worker owns an mp-device group and
            # runs its superstep shard_map'ed over a 1-D "model" mesh —
            # every shard dispatches its own program, each one
            # tensor-parallel inside.  The groups are the serving_mesh rows.
            from jax.sharding import Mesh

            from repro.distributed.sharding import model_group_placements

            groups = model_group_placements(shards, mp, devices)

        self.workers: List[ShardWorker] = []
        for i in range(shards):
            tp_kwargs = {}
            if groups is not None:
                tp_kwargs = dict(
                    model_mesh=Mesh(np.asarray(groups[i]), ("model",)),
                    param_specs=param_specs,
                    collective_payloads=self._collective_payloads,
                )
            w = ShardWorker(
                model_fn_factory, schedule, event_shape,
                num_slots=slots_local,
                seed=seed if i == 0 else seed + 1000003 * i,
                device=None if (devices is None or fused or mp > 1)
                else devices[i],
                shard_id=i,
                **worker_kwargs,
                **tp_kwargs,
            )
            # one per-(R, budget) executable pool for all shards — EXCEPT
            # per-shard TP, where each worker's programs are shard_map'ed
            # over its OWN device group's mesh and cannot be shared
            if i > 0 and groups is None:
                w.adopt_programs(self.workers[0])
            self.workers.append(w)
        self.schedule = schedule
        self.theta = self.workers[0].theta
        self.dropped_rids: list[int] = []
        self._wall_time = 0.0
        # the fused front end's single dispatch wall per boundary: a
        # FRONT-END lane (EngineStats.fused_dispatch_s on the merged view),
        # never split across the workers' per-shard dispatch_s
        self._fused_dispatch_s = 0.0
        # host seconds in the fused front end's admission block: a
        # front-end lane too (EngineStats.admit_s on the merged view)
        self._admit_s = 0.0
        self._tracer = worker_kwargs.get("tracer")
        self._routed = np.zeros((shards,), np.int64)  # router audit trail
        if fused:
            self._init_fused(devices)
        # one pending record per dispatch lane: each worker's own, or the
        # fused front end's for every shard at once
        self._lanes = ([Lane(self._dispatch_fused, self._harvest_fused,
                             self.workers)] if fused
                       else [w._lane for w in self.workers])
        log.debug("sharded engine up: %d shards x %d slots, dispatch=%s, "
                  "router=%s, mp=%d", shards, slots_local, dispatch,
                  self.router.name, mp)

    # -- fused dispatch: all shards in ONE shard_map program ----------------

    def _init_fused(self, devices) -> None:
        """Stack the workers' slot states into one (shards, slots_local, ...)
        pytree sharded over a ``slots`` mesh; workers keep all HOST state
        (queues, stats, weights, results) while the engine owns the device
        state and the fused executables.

        With ``model_shards > 1`` the mesh is the 2-D
        ``serving_mesh(shards, mp)`` (axes ``("slots", "model")``): slot
        state stays ``P("slots")``-sharded (replicated over the model axis),
        weights are placed by the ``tp_param_pspecs`` tree, and the fused
        superstep partitions over BOTH axes in the same single dispatch per
        boundary — the verify all-reduce runs inside the program."""
        from repro.distributed.sharding import (
            measure_collective_seconds_by_kind, serving_mesh, shard_pspecs,
            shardings_from_pspecs, slots_mesh)

        from jax.sharding import NamedSharding, PartitionSpec as P

        w0 = self.workers[0]
        mp = self.model_shards
        if mp > 1:
            self._mesh = serving_mesh(self.num_shards, mp, devices)
        else:
            self._mesh = slots_mesh(self.num_shards, devices)
        self._sharding = shard_pspecs(self._mesh)
        if w0._params is not None:
            # weights arriving on a DIFFERENT device set would be
            # incompatible inside one jit — re-place them here: replicated
            # over the slots mesh at mp=1 (in_specs P()), sharded by the
            # tp_param_pspecs tree over the "model" axis at mp>1.
            if self._param_specs is not None:
                rep_params = jax.device_put(
                    w0._params,
                    shardings_from_pspecs(self._mesh, self._param_specs))
            else:
                rep_params = jax.device_put(
                    w0._params, NamedSharding(self._mesh, P()))
            for w in self.workers:
                w._params = rep_params
        if mp > 1 and self._collective_payloads:
            # calibrate the per-round collective seconds, per kind, on the
            # live mesh and stamp every worker: the fused harvest reuses
            # the ordinary per-worker _harvest, which accounts R * this
            # per boundary into collective_s and the per-kind lanes
            points = (
                w0._budget_cap + (1 + w0.num_branches) * w0.num_slots
                if w0.execution == "packed"
                else w0.num_slots * (w0.theta * w0.num_branches + 1))
            by_kind = (self._collective_payloads
                       if isinstance(self._collective_payloads, dict)
                       else {"psum": list(self._collective_payloads)})
            kind_s = measure_collective_seconds_by_kind(
                self._mesh,
                {k: [int(b) * points for b in v]
                 for k, v in by_kind.items()})
            for w in self.workers:
                w._collective_kind_s = dict(kind_s)
                w._collective_s_per_round = sum(kind_s.values())
        stacked = jax.tree_util.tree_map(
            lambda *x: jnp.stack(x), *[w._states for w in self.workers])
        self._states = jax.device_put(
            stacked, shard_pspecs(self._mesh, stacked))
        self._conds = None
        self._conds_host = None
        if w0.d_cond:
            self._conds_host = np.zeros(
                (self.num_shards, w0.num_slots, w0.d_cond), np.float32)
            self._conds = jax.device_put(
                jnp.asarray(self._conds_host), self._sharding)
        for w in self.workers:  # fused reads only the host weight copies
            w._device_weights_live = False
        self._weights_versions = [-1] * self.num_shards
        self._weights_stacked = None
        self._refresh_weights()
        self._fused_fns: dict = {}

        S_local, shards = w0.num_slots, self.num_shards

        @jax.named_scope("asd/admit")
        def _admit(states, y0s, keys, flat_idxs):
            # one kind of a boundary's admissions for ALL shards: flatten
            # the shard axis, scatter, restore — states donated, sharding
            # re-pinned by out_shardings so the scatter cannot silently
            # replicate
            new = w0._fresh_chains(y0s, keys)
            flat = jax.tree_util.tree_map(
                lambda x: x.reshape((shards * S_local,) + x.shape[2:]), states)
            upd = jax.tree_util.tree_map(
                lambda b, n: b.at[flat_idxs].set(n), flat, new)
            return jax.tree_util.tree_map(
                lambda x: x.reshape((shards, S_local) + x.shape[1:]), upd)

        self._fused_admit = jax.jit(
            _admit, donate_argnums=(0,) if w0._donate else (),
            out_shardings=jax.tree_util.tree_map(
                lambda _: self._sharding, self._states))
        # padded admission widths ``_fused_admit`` has compiled -> kinds
        # (as ShardWorker._admit_widths)
        self._fused_admit_widths: dict = {}

    def _refresh_weights(self) -> None:
        """Restack the per-shard allocator weights when any worker changed
        one — a tiny (shards, slots_local) upload, only on change."""
        versions = [w._weights_version for w in self.workers]
        if versions != self._weights_versions:
            self._weights_versions = versions
            self._weights_stacked = jax.device_put(
                jnp.asarray(np.stack([w._weights for w in self.workers])),
                self._sharding)

    def _get_fused_superstep(self, R: int, budget):
        # budget-as-data (round_impl="fused"): one program per R; the
        # per-shard tiers arrive as a (shards,) vector, each shard peeling
        # its own scalar — different tiers inside ONE shard_map program
        as_data = self.workers[0]._budget_as_data
        key = (R, "data" if as_data else budget)
        fn = self._fused_fns.get(key)
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        from repro.core.asd import chain_sample

        w0 = self.workers[0]
        K, keep = w0.schedule.K, w0.keep_trajectory

        def one_shard(st, cond, w, p, b):
            # inside shard_map the shard axis has local size 1: peel it,
            # run this shard's superstep via the worker's ONE parameterized
            # body (_run_rounds — the same packed_superstep/asd_superstep
            # code the per-shard dispatch and the standalone
            # sharded_packed_superstep run, so all three stay bit-aligned),
            # re-stack for the out_specs.  Pack maps address only this
            # shard's rows.
            st1 = jax.tree_util.tree_map(lambda x: x[0], st)
            c1 = None if cond is None else cond[0]
            out = w0._run_rounds(
                st1, c1, p, w[0], R, budget if b is None else b[0])
            with jax.named_scope("asd/sync"):
                info = jnp.stack(
                    [getattr(out, f).astype(jnp.int32) for f in _SYNC_ROWS])
                samples = jax.vmap(lambda s: chain_sample(s, K, keep))(out)
            add = jax.tree_util.tree_map(lambda x: x[None], out)
            return add, info[None], samples[None]

        sh, rep = P("slots"), P()
        # params enter replicated at mp=1; at mp>1 the tp_param_pspecs tree
        # shards them over the mesh's "model" axis and the per-shard body
        # runs tensor-parallel (slot state never mentions "model", so it is
        # replicated across each shard's model group automatically)
        pp = rep if self._param_specs is None else self._param_specs
        has_conds = self._conds is not None
        if as_data:
            if has_conds:
                body = jax.shard_map(
                    lambda st, c, w, p, b: one_shard(st, c, w, p, b),
                    mesh=self._mesh, in_specs=(sh, sh, sh, pp, sh),
                    out_specs=(sh, sh, sh), check_vma=False)

                def fused(states, conds, p, weights, budgets):
                    return body(states, conds, weights, p, budgets)
            else:
                body = jax.shard_map(
                    lambda st, w, p, b: one_shard(st, None, w, p, b),
                    mesh=self._mesh, in_specs=(sh, sh, pp, sh),
                    out_specs=(sh, sh, sh), check_vma=False)

                def fused(states, conds, p, weights, budgets):
                    return body(states, weights, p, budgets)
        elif has_conds:
            body = jax.shard_map(
                lambda st, c, w, p: one_shard(st, c, w, p, None),
                mesh=self._mesh, in_specs=(sh, sh, sh, pp),
                out_specs=(sh, sh, sh), check_vma=False)

            def fused(states, conds, p, weights):
                return body(states, conds, weights, p)
        else:
            body = jax.shard_map(
                lambda st, w, p: one_shard(st, None, w, p, None),
                mesh=self._mesh, in_specs=(sh, sh, pp),
                out_specs=(sh, sh, sh), check_vma=False)

            def fused(states, conds, p, weights):
                return body(states, weights, p)

        donate = (0,) if w0._donate else ()
        fn = self._fused_fns[key] = jax.jit(fused, donate_argnums=donate)
        return fn

    def _span(self, name: str, **args) -> span:
        """A boundary span of the fused front end, on its lane of the ring."""
        return span(self._tracer, name, pid=self.num_shards,
                    tid=_FRONTEND_LANES[name], pname="frontend",
                    tname=name, **args)

    def _admit_fused(self) -> None:
        """Run each worker's admission policy and admit ALL placed chains
        in one program per kind (y0 drawn / given) across every shard."""
        now = time.perf_counter()
        with self._span("admit") as sp:
            rows = []
            S_local = self.workers[0].num_slots
            conds_touched = False
            for i, w in enumerate(self.workers):
                for slot, y0, key, cond_row in w._collect_admissions(now):
                    rows.append((i * S_local + slot, y0, key))
                    if cond_row is not None:
                        self._conds_host[i, slot] = cond_row
                        conds_touched = True
            if rows:
                self._states, width = dispatch_admissions(
                    self._states, rows, self._fused_admit,
                    self._fused_admit_widths, self._span, "fused_admit")
                if sp.on:
                    sp.set(admitted=len(rows), width=width)
                if conds_touched:
                    self._conds = jax.device_put(
                        jnp.asarray(self._conds_host), self._sharding)
        self._admit_s += time.perf_counter() - now

    def _dispatch_fused(self):
        """One boundary for every shard: admit at it, then launch ONE
        shard_map superstep covering every shard."""
        self._admit_fused()
        self._refresh_weights()
        # one common R across shards: worker 0 picks, siblings follow
        # (their admission contexts must quantize consistently).  The
        # budget is common too — UNLESS budget-as-data (round_impl=
        # "fused"), where each worker re-tiers independently and the
        # per-shard tiers ride into the one program as a sharded vector.
        R = self.workers[0]._pick_rounds()
        budget = self.workers[0]._pick_budget()
        for w in self.workers[1:]:
            w._rps = R
        fn = self._get_fused_superstep(R, budget)
        cold = getattr(fn, "_cache_size", lambda: 1)() == 0
        label = (self._span("compile", program=f"fused_superstep[R={R}]")
                 if cold else self._span(
                     "fused_dispatch", R=R,
                     budget=budget if budget is not None else 0))
        with label:
            t0 = time.perf_counter()
            if self.workers[0]._budget_as_data:
                budgets = np.asarray(
                    [budget] + [w._pick_budget() for w in self.workers[1:]],
                    np.int32)
                self._states, info, samples = fn(
                    self._states, self._conds, self.workers[0]._params,
                    self._weights_stacked, jnp.asarray(budgets))
            else:
                self._states, info, samples = fn(
                    self._states, self._conds, self.workers[0]._params,
                    self._weights_stacked)
            t1 = time.perf_counter()
        if not cold:
            # ONE front-end launch covers every shard: account it on the
            # engine's own fused-dispatch lane.  Splitting it across the
            # workers' dispatch_s (the old behavior) invented per-shard
            # launch time no worker ever spent and skewed every per-shard
            # timing_breakdown().
            self._fused_dispatch_s += t1 - t0
        snapshots = []
        for w in self.workers:
            w.stats.rounds_total += R
            w.stats.supersteps += 1
            snapshots.append(w.stats.rounds_total)
        return ((info, samples), snapshots, R, t0, cold)

    def _harvest_fused(self, pending) -> None:
        """Block once on the fused sync packet, then run every worker's
        ordinary harvest on its shard's slice (numpy views pass straight
        through the worker's device_get calls)."""
        (info, samples), snapshots, R, t0, cold = pending
        with self._span("fused_device_wait", R=R, cold=cold):
            t_wait = time.perf_counter()
            jax.block_until_ready(info)
            done_at = time.perf_counter()
        wait = done_at - t_wait
        with self._span("harvest"):
            info_np = np.asarray(jax.device_get(info))
            samples_np = np.asarray(jax.device_get(samples))
        for i, w in enumerate(self.workers):
            # one completion stamp for the whole boundary: worker i's
            # seconds-per-round EWMA must not absorb workers 0..i-1's
            # harvest bookkeeping (deadline admission reads that EWMA)
            w._harvest(((info_np[i], samples_np[i]),
                        snapshots[i], R, t0, cold), done_at=done_at)
            # the engine already paid the single blocking wait above (the
            # workers saw ready numpy views); spread it so the merged
            # timing stays the true total
            w.stats.device_s += wait / self.num_shards

    # -- views ---------------------------------------------------------------

    @property
    def stats(self) -> EngineStats:
        """Merged cross-shard view; per-shard stats at ``shard_stats``.
        The fused front end's dispatch wall rides on the merged view's
        ``fused_dispatch_s`` lane (workers never carry it)."""
        m = EngineStats.merged(
            [w.stats for w in self.workers], wall_time=self._wall_time)
        m.fused_dispatch_s += self._fused_dispatch_s
        m.admit_s += self._admit_s
        return m

    @property
    def shard_stats(self) -> List[EngineStats]:
        return [w.stats for w in self.workers]

    @property
    def round_budget(self):
        """Shard 0's current per-shard budget (tier) — the benchmark/report
        convenience view; per-shard tiers live on ``workers[i].round_budget``."""
        return self.workers[0].round_budget

    @property
    def routed_counts(self) -> np.ndarray:
        """Requests routed per shard (copy) — the router-contract metric."""
        return self._routed.copy()

    def has_work(self) -> bool:
        """Whether any shard has work queued or in a slot, or a superstep
        is still in flight."""
        return (any(w.scheduler.has_work() for w in self.workers)
                or any(ln.pending is not None for ln in self._lanes))

    @property
    def draining(self) -> bool:
        return any(w.draining for w in self.workers)

    def begin_drain(self) -> None:
        """Close every shard's admission gate: queued and in-flight
        requests finish (``serve``/``step`` keep draining), new
        submissions raise."""
        log.info("sharded engine draining %d shards", self.num_shards)
        for w in self.workers:
            w.begin_drain()

    def health(self) -> List[dict]:
        """Per-shard health/backpressure documents."""
        return [w.health() for w in self.workers]

    def healthz(self) -> dict:
        """The ``/healthz`` document: worst shard wins the status."""
        shards = self.health()
        if any(h["status"] == "draining" for h in shards):
            status = "draining"
        elif any(h["status"] == "backpressure" for h in shards):
            status = "backpressure"
        else:
            status = "ok"
        return {"status": status, "shards": shards}

    def chain_state(self, shard: int, slot: int):
        if self.dispatch == "fused":  # the engine owns the stacked state
            return jax.tree_util.tree_map(
                lambda x: x[shard, slot], self._states)
        return self.workers[shard].chain_state(slot)

    # -- request lifecycle ---------------------------------------------------

    def submit(self, request: Request) -> None:
        if self.draining:
            raise RuntimeError(
                f"engine is draining: request {request.rid} rejected "
                "(begin_drain() closed the admission gates)")
        shard = int(self.router.route(request, self.workers))
        if not 0 <= shard < self.num_shards:
            raise ValueError(
                f"router {self.router.name!r} returned shard {shard} "
                f"outside [0, {self.num_shards})")
        self._routed[shard] += 1
        now = time.perf_counter()
        tr = self._tracer
        if tr is not None and tr.enabled:
            tr.add_instant(
                "route", tr.from_perf(now), pid=self.num_shards, tid=2,
                pname="frontend", tname="router",
                args={"rid": request.rid, "shard": shard})
        self.workers[shard].scheduler.submit(request, now)

    def step(self) -> bool:
        """One superstep boundary across every shard with work, ordered as
        ``ContinuousASDEngine.step``: all launches of superstep s+1 (one
        per shard, or the one fused program) go out before any harvest of
        s, and each lane keeps s+1 in flight unless one of its shards has a
        request left queued.  Returns True while any shard has work queued,
        in a slot or in flight."""
        if not self.has_work():
            return False
        with self._span("step", superstep=self._supersteps()) as sp:
            sp.set(overlap=step_lanes(self._lanes))
        return self.has_work()

    def _supersteps(self) -> int:
        return max(w.stats.supersteps for w in self.workers)

    def serve(self, requests: List[Request], key=None) -> dict:
        """Submit everything through the router, ``step()`` until every
        shard has drained, return {rid: sample}.  With shards=1 this is
        exactly ``ContinuousASDEngine.serve``."""
        if key is not None:
            # every worker shares the SAME serve key: unkeyed requests
            # derive theirs as fold_in(key, rid), a pure function of the
            # request id — so the sample an unkeyed request gets does not
            # depend on which shard the router placed it on (rids are
            # globally unique; EngineStats.merged enforces that)
            for w in self.workers:
                w._key = key
        self.dropped_rids = []
        for w in self.workers:
            w.dropped_rids = []
        t0 = time.perf_counter()
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        if self.dispatch == "fused":
            jax.block_until_ready(self._states.a)
        else:
            for w in self.workers:
                jax.block_until_ready(w._states.a)
        self._wall_time += time.perf_counter() - t0
        out = {}
        for w in self.workers:
            out.update(w.drain_results())
            self.dropped_rids.extend(w.dropped_rids)
            w._refresh_health()
        if log.isEnabledFor(logging.INFO):
            m = self.stats
            log.info(
                "sharded serve drained: %d retired (%d dropped) across %d "
                "shards in %d supersteps", m.retired, m.dropped,
                self.num_shards, m.supersteps)
            for w, n in zip(self.workers, self._routed):
                log.debug("  shard %d: %d routed, %d retired, budget %s",
                          w.shard_id, int(n), w.stats.retired,
                          w.round_budget)
        return out

    def drain_results(self) -> dict:
        out = {}
        for w in self.workers:
            out.update(w.drain_results())
        return out

    def adopt_programs(self, warm) -> "ShardedASDEngine":
        """Share a warm engine's compiled programs (same statics and
        PER-SHARD shapes): benchmark repeats — and sweep arms with different
        shard counts but identical slots-per-shard — skip re-jit.  ``warm``
        may be another ``ShardedASDEngine`` (all of whose workers already
        share one executable pool) or a bare worker/engine."""
        donors = warm.workers if hasattr(warm, "workers") else [warm]
        warm_mp = getattr(warm, "model_shards", 1)
        if self.model_shards > 1 and self.dispatch == "per-shard" and (
                warm_mp != self.model_shards
                or getattr(warm, "num_shards", None) != self.num_shards):
            # per-shard TP programs are shard_map'ed over each worker's own
            # device-group mesh; only an identically-grouped engine's
            # executables can be reused
            return self
        for i, w in enumerate(self.workers):
            w.adopt_programs(donors[i % len(donors)])
        if self.dispatch == "fused" and getattr(warm, "dispatch", "") == (
                "fused") and warm.num_shards == self.num_shards and (
                warm_mp == self.model_shards):
            self._fused_fns = warm._fused_fns
            self._fused_admit = warm._fused_admit
            self._fused_admit_widths = warm._fused_admit_widths
        return self
