"""Shard-local serving worker: the device-side core of the ASD engines.

A ``ShardWorker`` owns everything ONE shard of a serving deployment needs:

  * a slot sub-batch of vmapped ``ASDChainState``s (optionally pinned to a
    single device or laid out by an explicit sharding),
  * the donated superstep executables that drive it, cached per
    ``(rounds_per_sync, round_budget)`` pair,
  * the boundary sync-packet harvest (retire flags, counters, samples in
    ONE transfer),
  * its own ``SlotScheduler`` admission queue and ``EngineStats``, and
  * the budget-allocator state (per-slot priority weights, live-demand
    EWMA, and — in auto mode — the power-of-two budget tier).

The worker is host-agnostic: it never routes requests and never talks to
other shards.  Everything cross-shard (request routing, per-shard budget
rebalancing, merged metrics) lives in the front ends —
``repro.serving.engine.ContinuousASDEngine`` (one worker, the classic
single-shard engine) and ``repro.serving.sharded.ShardedASDEngine`` (N
workers behind a pluggable ``Router``).  Because each worker packs its
verification points only across ITS OWN slots, pack maps are shard-local by
construction: growing the mesh never turns the packed gather into a
cross-host all-gather (ROADMAP "Multi-host serving").

Budget auto-tiering (``round_budget="auto"``, packed execution): the worker
tracks an EWMA of its live verification-point demand and re-picks its
``round_budget`` at superstep boundaries from a power-of-two ladder —
upshifts are immediate (demand is being trimmed NOW), downshifts take one
rung at a time and only once demand sits below ``budget_hysteresis`` of the
next tier down, so the tier never flaps around a noisy demand level.  The
EWMA also DECAYS at empty boundaries (zero live demand), so a drained burst
releases its tier instead of pinning the top rung forever.  Each tier
reuses the per-(R, budget) executable cache, which stays O(log * log)
entries (asserted) — and with ``round_impl="fused"`` the tier becomes DATA
(budget-as-data: the pack shape is the ladder cap, the tier a traced
scalar), collapsing the cache to one executable per R.
"""

from __future__ import annotations

import contextlib
import dataclasses
import logging
import time
from typing import Callable, Optional

log = logging.getLogger("repro.serving.worker")

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.asd import (
    ASDChainState,
    asd_superstep,
    chain_sample,
    init_chain_state,
)
from repro.core.controller import (
    BranchController,
    StaticBranches,
    StaticTheta,
    ThetaController,
)
from repro.core.schedules import Schedule
from repro.core.sequential import init_y0
from repro.serving.metrics import EngineStats, RequestMetrics
from repro.serving.obs.trace import span
from repro.serving.scheduler import (
    AdmissionContext,
    SchedulingPolicy,
    SlotScheduler,
)

# sync-packet row layout: the (9, S) int32 array each superstep returns next
# to the new slot states — retire flags, live windows, live branch counts,
# and the per-chain speculation counters, harvested with ONE host transfer
# per boundary
_SYNC_ROWS = ("a", "theta_live", "rounds", "head_calls", "model_evals",
              "accepts", "proposals", "b_live", "draft_points")

# ring lanes of the boundary spans, past the slot rows (tid = slots + lane);
# a compile takes the lane of the dispatch it stands in for
_LANES = {"dispatch": 0, "compile": 0, "device_wait": 1, "harvest": 2,
          "admit": 3, "step": 4}

# the power-of-two ladder auto rounds_per_sync picks from: O(log) compiled
# superstep variants instead of one per observed value
_AUTO_MAX_R = 16


@dataclasses.dataclass
class Request:
    rid: int
    cond: Optional[np.ndarray] = None  # (d_cond,) or None
    key: Optional[jax.Array] = None  # per-request PRNG key (else derived)
    y0: Optional[np.ndarray] = None  # explicit start state (else init_y0)
    priority: float = 0.0  # Priority policy: higher admits first
    deadline: Optional[float] = None  # absolute SLO deadline (perf_counter s)
    expected_accept_rate: Optional[float] = None  # SERR/deadline estimate hint


def _pow2_ladder(lo: int, hi: int) -> tuple:
    """Power-of-two rungs from the smallest pow2 >= lo, topped by ``hi``
    itself (the covering budget) where the next pow2 would overshoot —
    the top tier must cover every possible demand without padding the
    packed call past it (e.g. 8 slots x theta 6 tops at 48, not 64)."""
    tier = 1
    while tier < lo:
        tier *= 2
    ladder = [min(tier, hi)]
    while ladder[-1] < hi:
        ladder.append(min(ladder[-1] * 2, hi))
    return tuple(ladder)


def dispatch_admissions(states, rows, admit_fn, compiled: dict, span_fn,
                        program: str):
    """Admit one boundary's placed chains ``rows = [(idx, y0, key)]`` into
    ``states`` with one ``admit_fn(states, y0s, keys, idxs)`` dispatch per
    kind: first the rows whose start state the program draws from the key
    (``y0s`` None), then those that carry their own ``y0``.  The arguments
    are stacked with NumPy on the host, padded to a power of two by
    repeating row 0 (a duplicate scatter of the same record is a no-op) so
    the program has O(log S) variants of each kind.  The first dispatch of
    a (width, kind) is labelled a compile of ``program[width]``
    (``compiled`` maps width -> kinds seen).  Returns the new states and
    the width dispatched in all."""
    total = 0
    for drawn in (True, False):
        part = [row for row in rows if (row[1] is None) == drawn]
        if not part:
            continue
        width = 1 << (len(part) - 1).bit_length()
        idxs, y0s, keys = zip(*(part + [part[0]] * (width - len(part))))
        total += width
        kinds = compiled.setdefault(width, set())
        cold = drawn not in kinds
        kinds.add(drawn)
        with (span_fn("compile", program=f"{program}[{width}]") if cold
              else contextlib.nullcontext()):
            states = admit_fn(states, None if drawn else np.stack(y0s),
                              np.stack(keys), np.asarray(idxs, np.int32))
    return states, total


@dataclasses.dataclass(eq=False)
class Lane:
    """One double-buffered dispatch lane: ``dispatch()`` admits and launches
    a superstep and returns its harvest record, ``harvest(record)`` consumes
    it, ``workers`` are the shards whose queues and slots the lane serves,
    and ``pending`` is the record of the superstep still in flight."""

    dispatch: Callable
    harvest: Callable
    workers: list
    pending: Optional[tuple] = None

    def waiting(self, past_free: bool) -> bool:
        """Whether a request is queued on one of the lane's shards (beyond
        its free slots, with ``past_free``)."""
        return any(
            w.scheduler.queue_depth
            > (len(w.scheduler.free_slots()) if past_free else 0)
            for w in self.workers)

    def flush(self) -> None:
        if self.pending is not None:
            rec, self.pending = self.pending, None
            self.harvest(rec)


def step_lanes(lanes: list) -> int:
    """One superstep boundary over ``lanes``, double-buffered where nobody
    waits for a slot.  Returns how many lanes launched superstep s+1 before
    harvesting s.

    1. A lane with s in flight and more requests queued than free slots
       harvests s first, so the slots s freed are refilled at this boundary.
    2. Every lane whose scheduler has work admits and launches s+1 (all of
       them before any harvest, so the device programs are all in flight).
    3. Each lane harvests s while s+1 runs.
    4. A lane that still has a request queued after this boundary's
       admissions harvests s+1 too, so its slots refill at the next
       boundary; otherwise s+1 stays in flight until the next call.
    """
    for ln in lanes:
        if ln.waiting(past_free=True):
            ln.flush()
    launched = [ln.dispatch() if any(w.scheduler.has_work()
                                     for w in ln.workers) else None
                for ln in lanes]
    overlapped = 0
    for ln, rec in zip(lanes, launched):
        if ln.pending is not None and rec is not None:
            overlapped += 1
            for w in ln.workers:
                w.stats.overlapped_supersteps += 1
        ln.flush()
        ln.pending = rec
    for ln in lanes:
        if ln.waiting(past_free=False):
            ln.flush()
    return overlapped


class ShardWorker:
    """One shard's slot batch, superstep executables, and admission queue.

    Args:
      model_fn_factory: ``cond -> model_fn`` (or ``(params, cond) ->
        model_fn`` when ``params`` is given); ``cond`` is a traced (d_cond,)
        array when ``d_cond > 0``, else ``None``.
      schedule: the affine step schedule shared by all requests.
      event_shape: per-chain sample shape.
      num_slots: vmapped lanes of the per-round program ON THIS SHARD.
      theta: speculation window cap theta_max.
      params: optional model weight pytree, threaded through the per-round
        jit as an ARGUMENT.  Closure-captured weights would be baked into
        the executable as constants — re-processed on every standalone
        round dispatch (a measurable per-round tax on CPU) and forced
        replicated on a mesh; passing them as an argument keeps their
        sharding and makes the round program reuse device buffers.
      state_sharding: optional sharding pytree (matching ``ASDChainState``
        leaves with a leading slot axis) applied to the slot batch, e.g. from
        ``repro.distributed.sharding.chain_state_shardings``.  Takes
        precedence over ``device``.
      device: optional single device this shard's state, weights, and
        dispatches are pinned to — the topology handle the sharded engine
        uses to give each worker its own device
        (``repro.distributed.sharding.shard_placements``).
      controller: per-chain speculation-window controller (theta_live <=
        theta); a static config closed over by the jitted round, its state
        rides inside each slot's ``ASDChainState``.  Default: StaticTheta.
      num_branches: branched-speculation cap B — each round rolls up to B
        exchangeable draft branches per chain from the SAME proposal output
        and commits the branch with the longest accepted prefix (branch 0 is
        the canonical stream, so B=1 is bit-identical to unbranched).  With
        packed execution the branch axis multiplies each slot's point demand
        (``b_live * min(theta_live, K - a)``), so the budget ladder, the
        allocator's level scan, and admission pricing all size by
        ``theta * num_branches``.
      branch_controller: per-chain live-branch controller (b_live <= B),
        adapting the second speculation knob from the observed per-round
        branch gain.  Default: StaticBranches (always run the full cap).
      policy: host-side admission policy (``repro.serving.scheduler``) for
        THIS shard's queue.  Default: FCFS.
      grs_impl: "core" (pure-jnp verifier) or "kernel" (the Pallas GRS
        kernel; interpret-mode off-TPU, so CPU serving still works).
      execution: "unpacked" (one theta_max-shaped lane per slot) or "packed"
        (``repro.serving.packing``: each round gathers only the LIVE
        verification points across THIS SHARD'S slots into one
        ``round_budget``-shaped model call).
      round_budget: packed execution's verification points per round for
        this shard (>= num_slots; default slots * theta, i.e. never
        binding), or ``"auto"`` to re-pick the budget per superstep boundary
        from the live-demand EWMA on a power-of-two ladder with hysteresis.
      allocator: ``BudgetAllocator`` splitting the budget across slots
        (default: waterfilling).  Its priority weights come from
        ``Request.priority`` at admission.
      pack_impl: "ref" (jnp gather/scatter) or "kernel" (the Pallas pack
        kernel; backend-resolved via ``repro.kernels._backend``).
      round_impl: "packed" (default: the per-phase packed round body) or
        "fused" (packed execution only: each round's gather and
        verify/commit run through the fused kernel pair in
        ``repro.kernels.superstep``, and the round budget becomes DATA —
        the pack shape is the static cap, the tier a traced scalar, so the
        executable cache is keyed per R alone and auto-tiering never
        compiles per tier).  ``pack_impl`` picks the fused pair's
        ref/kernel lane.
      donate: donate the slot-state pytree to the superstep/admit dispatches
        (in-place buffer reuse).  Default (None): on for every backend
        EXCEPT cpu — the CPU PJRT runtime runs donated executions
        synchronously, which serializes the double-buffered boundary and
        books device execution time as dispatch time.
      rounds_per_sync: speculation rounds fused per device dispatch (the
        SUPERSTEP length R), or "auto" for the accept-rate ladder.
        Superstep dispatches DONATE the slot-state pytree to XLA, so the
        full ``ASDChainState`` batch is updated in place.
      overcommit: admission multiplexing factor (>= 1).  With packed
        execution, the nominal concurrency a budget supports is
        ``round_budget // theta_max`` full-width chains; ``overcommit > 1``
        lets ``BudgetAware`` admission fill slots up to ``overcommit`` times
        the budget's nominal demand — the allocator then multiplexes the
        admitted chains over the fixed budget (each runs a trimmed window)
        instead of leaving slots idle while requests queue.
      budget_hysteresis: auto-budget downshift threshold — the demand EWMA
        must sit at or below this fraction of the NEXT TIER DOWN before the
        tier drops a rung (upshifts are immediate).
      shard_id: this worker's index in a sharded deployment (0 for the
        single-shard engine); stamped on the worker's ``EngineStats``.
      tracer: optional ``repro.serving.obs.TraceRecorder``.  Every
        boundary opens ``repro.*`` profiler spans (``repro.serving.obs.span``:
        step, admit, dispatch or compile, device_wait, harvest); with a
        tracer they also land in its ring, one lane each past the slot rows,
        next to request-lifecycle spans (queued + request per slot), against
        pid = shard_id.  Tracing adds no device syncs.  ``None`` (default):
        the profiler spans alone.
      pipelined: deprecated alias kept for compatibility; ignored (every
        boundary overlaps when nobody waits for a slot, see ``step_lanes``).
    """

    def __init__(
        self,
        model_fn_factory: Callable,
        schedule: Schedule,
        event_shape: tuple,
        num_slots: int = 8,
        theta: int = 8,
        d_cond: int = 0,
        eager_head: bool = True,
        noise_mode: str = "buffer",
        keep_trajectory: bool = False,
        grs_impl: str = "core",
        params=None,
        state_sharding=None,
        pipelined: bool = False,
        seed: int = 0,
        controller: Optional[ThetaController] = None,
        num_branches: int = 1,
        branch_controller: Optional[BranchController] = None,
        policy: Optional[SchedulingPolicy] = None,
        execution: str = "unpacked",
        round_budget=None,
        allocator=None,
        pack_impl: str = "ref",
        round_impl: str = "packed",
        rounds_per_sync=1,
        overcommit: float = 1.0,
        budget_hysteresis: float = 0.75,
        donate: Optional[bool] = None,
        device=None,
        shard_id: int = 0,
        model_mesh=None,
        param_specs=None,
        collective_payloads=(),
        tracer=None,
    ):
        # Model parallelism: with ``model_mesh`` (a Mesh whose "model" axis
        # is this worker's device GROUP) the worker wraps every superstep in
        # shard_map over the group — params enter via ``param_specs``
        # (tp_param_pspecs / mp_param_pspecs layout: tensor-, expert- or
        # sequence-parallel), slot states / weights / conds replicate
        # across the group, and the parallelism-aware model fn runs its
        # psums / all_to_alls IN-PROGRAM, so the dispatch count per
        # boundary is unchanged.  ``collective_payloads`` (per-point
        # collective bytes of one model call — a {kind: [bytes...]} dict
        # from mp_collective_payloads, or a legacy flat psum list from
        # tp_collective_payloads) calibrates the EngineStats.collective_s
        # estimate (and its per-kind split) at init.
        self.schedule = schedule
        self.event_shape = tuple(event_shape)
        self.num_slots = num_slots
        self.theta = int(min(theta, schedule.K))
        self.d_cond = d_cond
        self.eager_head = eager_head
        self.noise_mode = noise_mode
        self.keep_trajectory = keep_trajectory
        self.grs_impl = grs_impl
        self.pipelined = pipelined
        self.pack_impl = pack_impl
        self.shard_id = shard_id
        self.device = device
        self._tracer = tracer
        self.draining = False  # graceful drain: submission gate is closed
        self.controller = controller if controller is not None else StaticTheta()
        self.num_branches = max(int(num_branches), 1)
        self.branch_controller = (
            branch_controller if branch_controller is not None
            else StaticBranches())
        if execution not in ("unpacked", "packed"):
            raise ValueError(f"unknown execution mode {execution!r}")
        self.execution = execution
        if round_impl not in ("packed", "fused"):
            raise ValueError(f"unknown round_impl {round_impl!r}")
        if round_impl == "fused" and execution != "packed":
            raise ValueError(
                'round_impl="fused" requires execution="packed" (the fused '
                "kernels run the packed round body)")
        self.round_impl = round_impl
        # donation makes the CPU runtime execute dispatches synchronously
        # (the aliased input buffer must be finalized before the call
        # returns), so the double-buffered boundary loses its overlap and the
        # dispatch timer absorbs the whole device execution — default it off
        # there, on everywhere else (TPU dispatch stays async)
        self._donate = (
            bool(donate) if donate is not None
            else jax.default_backend() != "cpu")
        if overcommit < 1.0:
            raise ValueError(f"overcommit must be >= 1, got {overcommit}")
        self.overcommit = float(overcommit)
        self.budget_hysteresis = float(budget_hysteresis)
        # the budget tier ladder: powers of two from the min viable budget
        # (>= num_slots: every live chain needs a point to make progress) up
        # to full coverage (slots * theta * branches).  Fixed budgets stay
        # off-ladder.
        self._budget_ladder = _pow2_ladder(
            num_slots, num_slots * self.theta * self.num_branches)
        if round_budget == "auto":
            if execution != "packed":
                raise ValueError(
                    'round_budget="auto" requires execution="packed" (the '
                    "unpacked engine has no budget-shaped call to re-tier)")
            self._budget_auto = True
            # open at the covering tier: adapting DOWN from safe is cheap,
            # opening undersized would trim every chain in the first wave
            self.round_budget = self._budget_ladder[-1]
        else:
            self._budget_auto = False
            self.round_budget = (
                num_slots * self.theta * self.num_branches
                if round_budget is None
                else int(round_budget)
            )
        if execution == "packed" and self.round_budget < num_slots:
            raise ValueError(
                f"round_budget {self.round_budget} < num_slots {num_slots}: "
                "every live chain needs at least one verification point per "
                "round to make progress")
        # budget-as-data (fused round): the pack shape is this static cap
        # (the ladder top in auto mode, the fixed budget otherwise); the
        # tier actually granted arrives at each dispatch as a traced scalar
        self._budget_as_data = round_impl == "fused"
        self._budget_cap = (
            self._budget_ladder[-1] if self._budget_auto
            else self.round_budget)
        if rounds_per_sync == "auto":
            self._auto_rps = True
            self._rps = 1  # last picked R; refreshed per boundary
        else:
            self._auto_rps = False
            self._rps = int(rounds_per_sync)
            if self._rps < 1:
                raise ValueError(
                    f"rounds_per_sync must be >= 1 or 'auto', got "
                    f"{rounds_per_sync!r}")
        self.scheduler = SlotScheduler(num_slots, policy=policy)
        self.stats = EngineStats(shard=shard_id)
        # this worker's dispatch lane (``step_lanes``); a fused front end
        # drives the workers through its own lane instead
        self._lane = Lane(self._dispatch_superstep, self._harvest, [self])
        # when the last harvested superstep was seen complete: a superstep
        # launched behind it starts on the device no earlier
        self._done_at = 0.0
        self._key = jax.random.PRNGKey(seed)
        self._results: dict[int, np.ndarray] = {}
        self.dropped_rids: list[int] = []
        # admission-context estimates: EWMAs of accept rate over retired
        # chains and of observed wall seconds per fused round.  Per-round
        # EWMA (not total-elapsed / rounds) so compile time and idle gaps
        # between serve() calls decay out instead of permanently inflating
        # the deadline policy's service-time estimates.
        self._accept_ewma = 1.0
        self._spr_ewma = 0.0
        # live verification-point demand of the slot batch, refreshed from
        # the same device sync the retirement scan already pays; feeds the
        # budget-pressure signal of the admission policies and (EWMA'd) the
        # auto budget tier
        self._live_demand = 0
        self._demand_ewma = 0.0
        # a fresh chain's opening demand (what one admission adds): the
        # controller's initial window times the opening branch count
        self._theta_open = int(self.controller.init(self.theta)[1])
        self._b_open = int(self.branch_controller.init(self.num_branches)[1])
        self._points_open = self._theta_open * max(self._b_open, 1)

        self._statics = dict(
            theta=self.theta,
            eager_head=eager_head,
            noise_mode=noise_mode,
            keep_trajectory=keep_trajectory,
            grs_impl=grs_impl,
            controller=self.controller,
            num_branches=self.num_branches,
            branch_controller=self.branch_controller,
        )
        self._model_mesh = model_mesh
        self._param_specs = param_specs
        self._collective_s_per_round = 0.0
        self._collective_kind_s: dict = {}
        if model_mesh is not None:
            from repro.distributed.sharding import (
                measure_collective_seconds_by_kind, shardings_from_pspecs)

            if params is None or param_specs is None:
                raise ValueError(
                    "model_mesh tensor parallelism needs explicit params AND "
                    "param_specs (a tp_param_pspecs tree) — a factory closure "
                    "cannot be sharded over the device group")
            params = jax.device_put(
                params, shardings_from_pspecs(model_mesh, param_specs))
            if collective_payloads:
                # calibrate the per-round collective estimate once, per
                # collective kind: the verify's psums / all_to_alls run
                # INSIDE the fused program, so their cost is probed with the
                # same payload schedule on the same group (~budget +
                # (1 + B)*slots points per packed round: verify lanes + the
                # plan's head call + the per-branch eager head lanes).
                # ``collective_payloads``: {kind: [bytes...]} from
                # mp_collective_payloads, or a legacy flat list (all psum).
                points = (
                    self._budget_cap + (1 + self.num_branches) * num_slots
                    if execution == "packed"
                    else num_slots * (self.theta * self.num_branches + 1))
                by_kind = (collective_payloads
                           if isinstance(collective_payloads, dict)
                           else {"psum": list(collective_payloads)})
                self._collective_kind_s = measure_collective_seconds_by_kind(
                    model_mesh,
                    {k: [int(b) * points for b in v]
                     for k, v in by_kind.items()})
                self._collective_s_per_round = sum(
                    self._collective_kind_s.values())
        self._params = params
        if params is None:
            self._make_fn = lambda p, cond: model_fn_factory(cond)
        else:
            self._make_fn = model_fn_factory  # (params, cond) -> model_fn

        if execution == "packed":
            from repro.serving.packing import WaterfillingAllocator

            # the waterfilling level scan must reach one slot's max demand,
            # which under branched speculation is theta * num_branches
            self.allocator = (
                allocator if allocator is not None
                else WaterfillingAllocator(
                    theta_max=self.theta * self.num_branches)
            )
        else:
            self.allocator = allocator

        K, keep = schedule.K, keep_trajectory

        def _make_superstep(R: int, budget: Optional[int]):
            # R fused rounds per dispatch + the boundary sync packet, built
            # on the public superstep API (asd_superstep / packed_superstep)
            # so the engine runs exactly the semantics the bit-exactness
            # tests pin.  The slot-state pytree is DONATED: XLA aliases the
            # output state buffers onto the inputs, so a superstep updates
            # the batch in place instead of allocating a fresh ASDChainState
            # copy per round.  The sync packet (fresh buffers: stack/gather
            # outputs) is everything the host needs at the boundary — retire
            # flags, live windows, counters, and each slot's final sample —
            # so no separate peek dispatch ever touches the (possibly
            # already donated-away) states.
            @jax.named_scope("asd/sync")
            def _pack_sync(states):
                info = jnp.stack(
                    [getattr(states, f).astype(jnp.int32) for f in _SYNC_ROWS]
                )
                samples = jax.vmap(
                    lambda st: chain_sample(st, K, keep))(states)
                return states, (info, samples)

            donate = (0,) if self._donate else ()
            if budget == "data":
                # budget-as-data: the tier is a TRACED call argument; one
                # executable serves the whole auto ladder
                def _superstep(states, conds, p, weights, budget_t):
                    return _pack_sync(self._run_rounds(
                        states, conds, p, weights, R, budget_t))
            else:
                def _superstep(states, conds, p, weights):
                    return _pack_sync(self._run_rounds(
                        states, conds, p, weights, R, budget))

            if self._model_mesh is not None:
                # Tensor-parallel superstep: shard_map over this worker's
                # model group.  Params enter SHARDED (tp_param_pspecs);
                # everything else is replicated across the group and stays
                # bitwise lockstep — the only cross-device data flow is the
                # model fn's in-program psums, whose reduction order is fixed
                # by the program, so replicated out_specs (check_vma=False)
                # are sound and the superstep is still ONE dispatch.
                rep = P()
                n_in = 5 if budget == "data" else 4
                in_specs = [rep] * n_in
                in_specs[2] = self._param_specs
                _superstep = jax.shard_map(
                    _superstep, mesh=self._model_mesh,
                    in_specs=tuple(in_specs), out_specs=rep,
                    check_vma=False)
            return jax.jit(_superstep, donate_argnums=donate)

        self._make_superstep = _make_superstep
        # one executable per (R, budget) pair; the auto modes draw both
        # coordinates from power-of-two ladders so this stays O(log * log)
        self._superstep_fns: dict[tuple, Callable] = {}
        self._compiled_supersteps = 0  # this worker's own cache misses
        self._weights = np.ones((num_slots,), np.float32)
        self._weights_version = 0  # bumped per change: fused-mode restack cue
        # device copy of the allocator weights: updated IN PLACE one lane at
        # a time when an admission/retire changes a slot's priority — never
        # re-uploaded wholesale from the host.  A fused front end reads only
        # the host copy (it restacks across shards) and clears this flag so
        # the per-lane device update isn't paid for nothing.
        self._device_weights_live = True
        self._weights_dev = jnp.asarray(self._weights)
        if device is not None:
            self._weights_dev = jax.device_put(self._weights_dev, device)
        elif model_mesh is not None:
            self._weights_dev = jax.device_put(
                self._weights_dev, NamedSharding(model_mesh, P()))

        @jax.named_scope("asd/admit")
        def _admit(states, y0s, keys, idxs):
            # draw + init + scatter for one kind of a boundary's admissions
            # in ONE dispatch; states donated — the scatter reuses the slot
            # buffers
            new_sts = self._fresh_chains(y0s, keys)
            return jax.tree_util.tree_map(
                lambda b, n: b.at[idxs].set(n), states, new_sts
            )

        self._admit_fn = jax.jit(
            _admit, donate_argnums=(0,) if self._donate else ())
        # padded admission widths ``_admit_fn`` has compiled, each with the
        # kinds compiled at it (True: y0 drawn in the program, False: y0
        # given): an admission of a new (width, kind) is labelled a compile
        self._admit_widths: dict = {}

        # All slots start as already-finished dummy chains: frozen under
        # asd_round until a real request is admitted over them.
        K = schedule.K
        self._states = self._fresh_chains(
            jnp.zeros((num_slots,) + self.event_shape),
            jax.random.split(jax.random.PRNGKey(seed), num_slots))
        self._states = dataclasses.replace(
            self._states, a=jnp.full((num_slots,), K, jnp.int32)
        )
        self._conds = (
            jnp.zeros((num_slots, d_cond), jnp.float32) if d_cond else None
        )
        if state_sharding is not None:
            self._states = jax.device_put(self._states, state_sharding)
        elif device is not None:
            self._states = jax.device_put(self._states, device)
        elif model_mesh is not None:
            # slot states replicate across the model group (every group
            # device runs the full slot batch in lockstep)
            rep = NamedSharding(model_mesh, P())
            self._states = jax.device_put(self._states, rep)
            if self._conds is not None:
                self._conds = jax.device_put(self._conds, rep)
        log.debug(
            "shard %d worker up: slots=%d theta=%d execution=%s budget=%s "
            "R=%s policy=%s", shard_id, num_slots, self.theta, execution,
            "auto" if self._budget_auto else self.round_budget,
            "auto" if self._auto_rps else self._rps,
            self.scheduler.policy.name)

    # -- the ONE superstep body both execution modes share -------------------

    def _run_rounds(self, states, conds, p, weights, R: int, budget):
        """R fused rounds over the slot batch — the single parameterized
        superstep body.  Packed execution budgets the per-round model call
        (shapes depend on the static (R, budget) pair); unpacked vmaps the
        theta_max-shaped per-slot superstep and ignores the budget.  With
        ``round_impl="fused"``, ``budget`` may be a TRACED tier — the pack
        shape is the static ``_budget_cap`` and the tier rides as data."""
        if self.execution == "packed":
            from repro.serving.packing import packed_superstep

            if self._budget_as_data:
                return packed_superstep(
                    self._make_fn, p, self.schedule, states, conds, weights,
                    rounds=R, budget=self._budget_cap, budget_data=budget,
                    allocator=self.allocator, pack_impl=self.pack_impl,
                    round_impl="fused", **self._statics,
                )
            return packed_superstep(
                self._make_fn, p, self.schedule, states, conds, weights,
                rounds=R, budget=budget, allocator=self.allocator,
                pack_impl=self.pack_impl, round_impl=self.round_impl,
                **self._statics,
            )

        def one(st, cond):
            return asd_superstep(
                self._make_fn(p, cond), self.schedule, st, rounds=R,
                **self._statics)

        if conds is None:
            return jax.vmap(lambda st: one(st, None))(states)
        return jax.vmap(one)(states, conds)

    # -- request lifecycle ---------------------------------------------------

    def _request_key(self, rid: int) -> jax.Array:
        """PRNG key for a request submitted WITHOUT one: the worker's serve
        key folded on the request id.  A pure function of (serve key, rid)
        — NOT of admission order, slot index, shard placement, or
        re-admission after a drain — so the sample an unkeyed request gets
        is pinned by its id alone, and the chain's branch draws (which fold
        off this key) stay slot-independent.  The old derivation (splitting
        a mutable engine key per admission) tied every sample to the exact
        admission sequence: re-running the same request set in a different
        arrival order, or re-admitting one request, silently changed
        OTHER requests' samples."""
        return jax.random.fold_in(self._key, int(rid) & 0xFFFFFFFF)

    def _fresh_chains(self, y0s, keys):
        """Fresh chain states for one batch of admissions, traced inside an
        admission program.  ``y0s`` None draws each row's start state from
        its key first — ``key, k0 = split(key)``, ``y0 = init_y0(k0)`` —
        the same bits a per-request draw on the host gives."""
        def one(y0, key):
            if y0 is None:
                key, k0 = jax.random.split(key)
                y0 = init_y0(self.schedule, k0, self.event_shape)
            return init_chain_state(
                self.schedule, y0, key, self.theta, self.noise_mode,
                self.keep_trajectory, self.controller,
                num_branches=self.num_branches,
                branch_controller=self.branch_controller)

        return jax.vmap(one)(y0s, keys)

    def _admission_context(self, now: float) -> AdmissionContext:
        return AdmissionContext(
            K=self.schedule.K,
            theta_max=self.theta,
            accept_rate=self._accept_ewma,
            seconds_per_round=self._spr_ewma,
            now=now,
            round_budget=self.round_budget,
            live_demand=self._live_demand,
            theta_open=self._points_open,
            rounds_per_sync=self._rps,
            overcommit=self.overcommit,
        )

    @property
    def load(self) -> float:
        """Occupancy + queue pressure on this shard, in units of full slot
        batches: 0 = idle, 1 = every slot busy, > 1 = requests queueing.
        The routing signal ``LeastLoaded`` balances on."""
        busy = self.num_slots - len(self.scheduler.free_slots())
        return (busy + self.scheduler.queue_depth) / max(self.num_slots, 1)

    def has_work(self) -> bool:
        """Whether a request is queued or in a slot, or a superstep is
        still in flight."""
        return self.scheduler.has_work() or self._lane.pending is not None

    # -- health / drain ------------------------------------------------------

    def begin_drain(self) -> None:
        """Close the admission gate: in-flight and queued requests finish
        (``serve``/``step`` keep draining them), new submissions raise —
        the graceful-drain half of the router health contract."""
        if not self.draining:
            self.draining = True
            self.stats.draining = True
            log.info("shard %d draining: %d queued, %d active",
                     self.shard_id, self.scheduler.queue_depth,
                     len(self.scheduler.active_slots()))

    def _refresh_health(self) -> None:
        """Stamp the live health/backpressure signals onto ``stats`` —
        a handful of host integer reads, paid at harvest boundaries and on
        ``health()`` calls."""
        s = self.stats
        sched = self.scheduler
        s.queue_depth = sched.queue_depth
        s.queue_depth_peak = max(s.queue_depth_peak, sched.queue_depth_peak)
        s.slot_occupancy = (
            (self.num_slots - len(sched.free_slots()))
            / max(self.num_slots, 1))
        s.admission_pressure = self._admission_context(
            time.perf_counter()).budget_pressure
        s.draining = self.draining

    def health(self) -> dict:
        """This shard's health/backpressure document.  ``saturated`` means
        more than a full slot batch is queued behind the busy slots — the
        backpressure signal ``/healthz`` turns into a 503."""
        self._refresh_health()
        s = self.stats
        saturated = s.queue_depth > self.num_slots
        status = ("draining" if self.draining
                  else "backpressure" if saturated else "ok")
        return {
            "status": status,
            "shard": self.shard_id,
            "queue_depth": s.queue_depth,
            "queue_depth_peak": s.queue_depth_peak,
            "slot_occupancy": s.slot_occupancy,
            "admission_pressure": s.admission_pressure,
            "draining": self.draining,
            "saturated": saturated,
        }

    def healthz(self) -> dict:
        """The ``/healthz`` document for a single-worker deployment."""
        h = self.health()
        return {"status": h["status"], "shards": [h]}

    # -- superstep machinery -------------------------------------------------

    def _get_superstep(self, R: int, budget):
        # budget-as-data: one program per R serves every tier — the budget
        # coordinate collapses to the sentinel "data"
        key = (R, "data" if self._budget_as_data else budget)
        fn = self._superstep_fns.get(key)
        if fn is None:
            fn = self._superstep_fns[key] = self._make_superstep(R, key[1])
            # the auto ladders bound the program count: O(log R * log budget).
            # Count THIS worker's compiles, not the pool size — the pool is
            # shared across siblings (adopt_programs) whose statics differ,
            # so its total length is legitimately larger than one worker's
            # ladder bound.
            self._compiled_supersteps += 1
            max_r = (_AUTO_MAX_R.bit_length() if self._auto_rps else 1)
            max_b = (
                1 if self._budget_as_data
                else len(self._budget_ladder) if self._budget_auto else 1)
            assert self._compiled_supersteps <= max_r * max_b + 1, (
                f"worker compiled more superstep programs than its ladders "
                f"allow: {sorted(self._superstep_fns)}")
        return fn

    def _pick_rounds(self) -> int:
        """The superstep length for the next dispatch.

        Fixed mode returns the configured R.  Auto mode sizes R to the
        accept-rate EWMA: a fresh chain is expected to run about
        K / E[advance] rounds (geometric accept model, the same estimate the
        deadline policy uses); R is chosen so a chain that retires
        mid-superstep idles its slot for at most ~1/8 of that service time,
        then snapped DOWN to the power-of-two ladder so only O(log) superstep
        programs ever compile.
        """
        if not self._auto_rps:
            return self._rps
        p = min(max(self._accept_ewma, 0.0), 0.999)
        adv = (1.0 - p ** self.theta) / max(1.0 - p, 1e-3)
        exp_rounds = self.schedule.K / max(adv, 1.0)
        target = max(1, int(exp_rounds / 8.0))
        R = 1
        while R * 2 <= min(target, _AUTO_MAX_R):
            R *= 2
        self._rps = R
        return R

    def _pick_budget(self) -> Optional[int]:
        """The verification budget for the next dispatch.

        Fixed mode returns the configured budget (None on the unpacked
        path, where no call is budget-shaped).  Auto mode tracks the
        live-demand EWMA on the power-of-two ladder: upshift straight to
        the covering tier (demand above the tier means every chain's window
        is being trimmed RIGHT NOW), downshift one rung at a time and only
        once demand sits at or below ``budget_hysteresis`` of the next tier
        down — the hysteresis band keeps a noisy demand level from flapping
        the tier (and recompiling nothing, but re-warming caches) every
        boundary.
        """
        if self.execution != "packed":
            return None
        if not self._budget_auto:
            return self.round_budget
        demand = max(self._demand_ewma, 1.0)
        target = self._budget_ladder[-1]
        for tier in self._budget_ladder:
            if tier >= demand:
                target = tier
                break
        cur = self.round_budget
        if target > cur:
            self.round_budget = target
        elif target < cur and cur > self._budget_ladder[0]:
            lower = max(t for t in self._budget_ladder if t < cur)
            if self._demand_ewma <= self.budget_hysteresis * lower:
                self.round_budget = lower
        if self.round_budget != cur:
            log.debug(
                "shard %d budget tier %d -> %d (demand ewma %.1f)",
                self.shard_id, cur, self.round_budget, self._demand_ewma)
        return self.round_budget

    def _set_weight(self, slot: int, w: float) -> None:
        """One-lane device update of the allocator priority weights — no
        full host->device re-upload on the admission/retire paths."""
        if self._weights[slot] != w:
            self._weights[slot] = w
            self._weights_version += 1
            if self._device_weights_live:
                self._weights_dev = self._weights_dev.at[slot].set(w)

    def _observe_round_time(self, dt: float) -> None:
        # cold (compiling) dispatches never reach here — see
        # _harvest — so the EWMA only sees real round walls
        self._spr_ewma = dt if self._spr_ewma == 0.0 else (
            0.7 * self._spr_ewma + 0.3 * dt)

    def _collect_admissions(self, now: float):
        """One boundary's admission POLICY + host bookkeeping, device-free:
        run the scheduler, account drops/weights/demand, and return the
        scatter batch ``[(slot, y0, key, cond_row)]`` (empty when nothing
        was placed), ``y0`` None where the admission program draws it from
        the key.  The caller owns the device program — per-worker
        (``_admit_pending``) or fused across shards
        (``ShardedASDEngine._dispatch_fused``)."""
        placed = self.scheduler.admit(
            now, self.stats.rounds_total, self._admission_context(now)
        )
        for entry in self.scheduler.drain_dropped():
            self.stats.observe_drop()
            self.dropped_rids.append(entry.request.rid)
            log.info("shard %d dropped rid=%s at admission "
                     "(deadline unmeetable)",
                     self.shard_id, entry.request.rid)
        batch = []
        for slot, req in placed:
            key = (req.key if req.key is not None
                   else self._request_key(req.rid))
            y0 = (None if req.y0 is None
                  else np.asarray(req.y0, np.float32))
            cond_row = None
            if self.d_cond:
                cond_row = (np.zeros((self.d_cond,), np.float32)
                            if req.cond is None
                            else np.asarray(req.cond, np.float32))
            # allocator priority weight: 1 + the request's priority (>= a
            # small floor so zero/negative priorities still get budget)
            self._set_weight(
                slot,
                max(1.0 + float(getattr(req, "priority", 0.0) or 0.0), 0.1))
            # a fresh chain opens at the controller's initial window times
            # its opening branch count: count that into the live demand the
            # budget-pressure signal sees
            self._live_demand += self._points_open
            self.stats.requests += 1
            batch.append((slot, y0, key, cond_row))
        return batch

    def _span(self, name: str, **args) -> span:
        """A boundary span of this shard, on its lane of the ring."""
        return span(self._tracer, name, pid=self.shard_id,
                    tid=self.num_slots + _LANES[name],
                    pname=f"shard-{self.shard_id}", tname=name, **args)

    def _admit_pending(self) -> None:
        t0 = time.perf_counter()
        with self._span("admit") as sp:
            batch = self._collect_admissions(t0)
            if batch:
                if self.d_cond:
                    conds = np.array(self._conds)
                    for slot, _, _, cond_row in batch:
                        conds[slot] = cond_row
                self._states, width = dispatch_admissions(
                    self._states, [row[:3] for row in batch],
                    self._admit_fn, self._admit_widths, self._span, "admit")
                if sp.on:
                    sp.set(admitted=len(batch), width=width, rids=[
                        self.scheduler.slot_info(slot).request.rid
                        for slot, _, _, _ in batch])
                if self.d_cond:
                    self._conds = jnp.asarray(conds)
        self.stats.admit_s += time.perf_counter() - t0

    def _dispatch_superstep(self):
        """Admit at the boundary, launch one superstep, return its pending
        harvest record (sync packet + the round count it reflects)."""
        self._admit_pending()
        R = self._pick_rounds()
        B = self._pick_budget()
        fn = self._get_superstep(R, B)
        # a cold executable means THIS call pays the jit compile: keep that
        # one-off out of dispatch_s and the seconds-per-round EWMA, or (in
        # auto mode especially, which compiles ladder entries mid-traffic)
        # the deadline policy's service-time estimate balloons and drops
        # meetable requests — and drops are final.  _cache_size is a private
        # jax accessor: degrade to "warm" if an upgrade drops it
        cold = getattr(fn, "_cache_size", lambda: 1)() == 0
        label = (self._span("compile", program=f"superstep[R={R},B={B}]")
                 if cold else self._span("dispatch", R=R, budget=B))
        with label:
            t0 = time.perf_counter()
            if self._budget_as_data:
                self._states, sync = fn(
                    self._states, self._conds, self._params,
                    self._weights_dev, np.int32(B))
            else:
                self._states, sync = fn(
                    self._states, self._conds, self._params,
                    self._weights_dev)
            t1 = time.perf_counter()
        if not cold:
            self.stats.dispatch_s += t1 - t0
        self.stats.rounds_total += R
        self.stats.supersteps += 1
        return (sync, self.stats.rounds_total, R, t0, cold)

    def _harvest(self, pending, done_at: Optional[float] = None) -> None:
        """Consume one superstep's sync packet: retire every chain that
        finished during it (flags, counters, AND samples ride in the packet
        — no peek dispatch against possibly-donated state buffers), refresh
        the budget-pressure signal, and update the service-time EWMAs.

        ``snapshot_rounds`` is the engine round count the packet reflects:
        slots admitted at or after it hold a chain NOT yet present in the
        packet (whose lane still shows the previous, finished occupant) and
        must not be retired against it — a boundary that overlaps harvests
        the packet one superstep behind the dispatch frontier.
        """
        sync, snapshot_rounds, R, t_dispatch, cold = pending
        info_dev, samples_dev = sync
        tr = self._tracer
        if tr is not None and not tr.enabled:
            tr = None
        with self._span("device_wait", R=R, cold=cold):
            t0 = time.perf_counter()
            jax.block_until_ready(info_dev)  # off the critical path when
            t1 = time.perf_counter()  # the next superstep is in flight
        self.stats.device_s += t1 - t0
        with self._span("harvest") as sp:
            finished = self._retire(info_dev, samples_dev, snapshot_rounds,
                                    R, cold, tr)
            self.stats.host_sync_s += time.perf_counter() - t1
            self._refresh_health()
            sp.set(retired=len(finished), live_demand=self._live_demand)
        # a superstep launched while its predecessor ran starts on the
        # device when that one completes: time it from the later of the two
        start = max(t_dispatch, self._done_at)
        self._done_at = done_at if done_at is not None else t1
        if not cold:  # a cold dispatch's elapsed time is mostly jit compile
            # ``done_at``: a fused front end passes ONE completion stamp for
            # the whole boundary, so later shards' EWMAs aren't inflated by
            # their siblings' harvest bookkeeping running first
            end = done_at if done_at is not None else time.perf_counter()
            self._observe_round_time((end - start) / R)

    def _retire(self, info_dev, samples_dev, snapshot_rounds: int, R: int,
                cold: bool, tr) -> list:
        """The harvest's host side: read the sync packet, refresh the
        live-demand signals, retire every finished chain.  Returns the
        retired slots."""
        if self._collective_s_per_round and not cold:
            # calibrated estimate: the TP all-reduces run INSIDE the fused
            # superstep (one psum-probe wall per round, measured at init on
            # this group's devices), so attribute probe x R per boundary
            self.stats.collective_s += R * self._collective_s_per_round
            self.stats.collective_psum_s += (
                R * self._collective_kind_s.get("psum", 0.0))
            self.stats.collective_a2a_s += (
                R * self._collective_kind_s.get("all_to_all", 0.0))
        info = np.asarray(jax.device_get(info_dev))
        row = {name: info[i] for i, name in enumerate(_SYNC_ROWS)}
        a, theta_live = row["a"], row["theta_live"]
        now = time.perf_counter()
        K = self.schedule.K
        # refresh the budget-pressure signal off the sync we already pay:
        # live demand = sum over active slots of b_live * min(theta_live,
        # K - a) — each live branch wants its own copy of the window
        occupied = np.zeros((self.num_slots,), bool)
        occupied[self.scheduler.active_slots()] = True
        live = occupied & (a < K)
        b_live = np.maximum(row["b_live"], 1)
        self._live_demand = int(
            (b_live[live]
             * np.minimum(theta_live[live], (K - a)[live])).sum())
        # the auto budget tier tracks demand through an EWMA, not the raw
        # sample.  Empty boundaries DECAY it multiplicatively instead of
        # blending in the zero: one momentary gap cannot collapse the tier
        # (the downshift path drops a single rung per boundary anyway), but
        # a drained burst stops pinning the top tier — after a couple of
        # idle boundaries the EWMA clears the hysteresis band and the next
        # trickle of traffic reopens at a demand-sized tier
        if self._live_demand == 0:
            self._demand_ewma *= 0.5
        else:
            self._demand_ewma = (
                float(self._live_demand) if self._demand_ewma == 0.0
                else 0.5 * self._demand_ewma + 0.5 * self._live_demand)
        finished = [
            slot for slot in self.scheduler.active_slots()
            if self.scheduler.slot_info(slot).admit_round < snapshot_rounds
            and a[slot] >= K
        ]
        if finished:
            samples = np.asarray(jax.device_get(samples_dev))
            for slot in finished:
                sinfo = self.scheduler.retire(slot)
                self._set_weight(slot, 1.0)
                self._results[sinfo.request.rid] = np.asarray(samples[slot])
                if tr is not None:
                    rid = sinfo.request.rid
                    tr.add_span(
                        "queued", tr.from_perf(sinfo.submit_time),
                        tr.from_perf(sinfo.admit_time),
                        pid=self.shard_id, tid=slot,
                        pname=f"shard-{self.shard_id}",
                        tname=f"slot-{slot}", args={"rid": rid})
                    tr.add_span(
                        "request", tr.from_perf(sinfo.admit_time),
                        tr.from_perf(now),
                        pid=self.shard_id, tid=slot,
                        args={"rid": rid,
                              "rounds": int(row["rounds"][slot]),
                              "accepts": int(row["accepts"][slot]),
                              "theta_live": int(theta_live[slot])})
                deadline = getattr(sinfo.request, "deadline", None)
                rm = RequestMetrics(
                    rid=sinfo.request.rid,
                    queue_latency=sinfo.admit_time - sinfo.submit_time,
                    service_time=now - sinfo.admit_time,
                    rounds=int(row["rounds"][slot]),
                    head_calls=int(row["head_calls"][slot]),
                    model_evals=int(row["model_evals"][slot]),
                    accepts=int(row["accepts"][slot]),
                    proposals=int(row["proposals"][slot]),
                    draft_points=int(row["draft_points"][slot]),
                    deadline=deadline,
                    slo_met=None if deadline is None else now <= deadline,
                )
                self.stats.observe(rm)
                # EWMA over retired chains feeds SERR/deadline estimates
                self._accept_ewma = (
                    0.8 * self._accept_ewma + 0.2 * rm.accept_rate)
        if not self.scheduler.active_slots() and (
                self.scheduler.queue_depth == 0):
            # the shard went fully idle: no further harvests will run, so
            # the EWMA would otherwise FREEZE at the drained burst's level
            # and pin the auto tier at the top rung until the next traffic
            # paid burst-sized supersteps.  Reset the demand signal — the
            # next admission re-tiers from ITS OWN demand.
            self._live_demand = 0
            self._demand_ewma = 0.0
        return finished

    def drain_results(self) -> dict:
        out, self._results = self._results, {}
        return out

    def adopt_programs(self, warm: "ShardWorker") -> "ShardWorker":
        """Share a warm worker's compiled programs (same statics/shapes):
        sibling shards and benchmark repeats reuse executables instead of
        re-paying jit — the cache is keyed per (R, budget), so every shard
        of a sharded engine draws from ONE pool."""
        self._make_superstep = warm._make_superstep
        self._superstep_fns = warm._superstep_fns
        self._admit_fn = warm._admit_fn
        self._admit_widths = warm._admit_widths
        return self

    def chain_state(self, slot: int) -> ASDChainState:
        """Debug view of one slot's resumable state."""
        return jax.tree_util.tree_map(lambda x: x[slot], self._states)
