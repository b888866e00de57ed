"""Per-request and engine-level serving metrics.

``RequestMetrics`` is emitted once per retired chain; the per-chain speculation
counters (rounds, head calls, accepts, proposals) come straight off the
``ASDChainState`` — they are exact because ``asd_round`` freezes a finished
chain's counters while its slot waits to be retired.

``EngineStats`` aggregates across requests and keeps the engine-level counters
(fused rounds driven, wall time) that the throughput benchmark and the
system tests read.  In a sharded deployment each ``ShardWorker`` keeps its
own ``EngineStats`` (stamped with its ``shard`` id) and the front end
presents ``EngineStats.merged(...)`` — counters and timing components SUM
across shards (shards burn host/device time independently), while
``wall_time`` is the front end's single wall clock (shards run
concurrently, so summing their walls would double-count real time).
"""

from __future__ import annotations

import dataclasses
import math
from collections import Counter
from typing import List, Optional, Sequence


@dataclasses.dataclass
class RequestMetrics:
    rid: int
    queue_latency: float  # submit -> admit (s)
    service_time: float  # admit -> retire (s)
    rounds: int  # speculation rounds this chain ran
    head_calls: int  # sequential proposal calls actually made
    model_evals: int  # total model evaluations (all speculation slots)
    accepts: int
    proposals: int
    draft_points: int = 0  # verification points drafted across ALL branches
    deadline: Optional[float] = None  # absolute SLO deadline, if any
    slo_met: Optional[bool] = None  # retired before the deadline? (None: no SLO)

    @property
    def accept_rate(self) -> float:
        return self.accepts / max(self.proposals, 1)

    @property
    def branch_accept_depth(self) -> float:
        """Mean accepted prefix length per round — the branched-speculation
        win shows up here: extra draft branches deepen the accepted prefix
        without changing the round count semantics."""
        return self.accepts / max(self.rounds, 1)

    @property
    def wasted_draft_frac(self) -> float:
        """Fraction of drafted verification points that never committed.
        With one branch ``draft_points == proposals`` and this equals
        ``1 - accept_rate``; extra branches draft more points per round, so
        the waste rises with B while the accept depth (hopefully) rises too
        — the two lanes together price the branch trade-off."""
        if self.draft_points <= 0:
            return 0.0
        return 1.0 - self.accepts / self.draft_points

    @property
    def parallel_depth(self) -> int:
        """Sequential model-call depth this chain experienced."""
        return self.rounds + self.head_calls

    @property
    def latency(self) -> float:
        return self.queue_latency + self.service_time

    @property
    def mean_window(self) -> float:
        """Mean live speculation window (verified slots per round) — equals
        theta under StaticTheta, tracks theta_live under adaptive control."""
        return self.proposals / max(self.rounds, 1)


@dataclasses.dataclass
class EngineStats:
    requests: int = 0  # admitted into the engine
    retired: int = 0  # completed and returned
    batches: int = 0  # chunked engine: batches launched
    rounds_total: int = 0  # fused engine rounds driven (all slots at once)
    supersteps: int = 0  # device dispatches (each runs rounds_per_sync rounds)
    # supersteps launched while their predecessor was still to be harvested
    # (the boundary overlapped: nobody was waiting for a slot)
    overlapped_supersteps: int = 0
    # where the engine's wall time goes, per superstep boundary:
    #   dispatch_s   host time spent launching the jitted superstep — the
    #                async call, not its execution
    #   device_s     host time blocked waiting for a superstep's results to
    #                become ready (block_until_ready on the sync packet)
    #   host_sync_s  host time transferring the sync packet + retire/metrics
    #                bookkeeping — the per-boundary tax supersteps amortize
    #   collective_s model-parallel all-reduce seconds INSIDE the superstep
    #                programs (a per-round probe calibration on the worker's
    #                device group x rounds driven, see ShardWorker) — a view
    #                INTO device execution, not a fourth wall component: the
    #                device already pays this time inside the fused program,
    #                so it never joins the accounted total below
    #   fused_dispatch_s  the sharded FUSED front end's single dispatch wall
    #                per boundary (one shard_map program covers every shard).
    #                It is a FRONT-END lane, never split across workers: the
    #                per-shard dispatch_s above must not invent per-shard
    #                launch time a worker never spent.
    #   admit_s      host time admitting chains at a boundary: the
    #                admission policy plus its one program per kind (the
    #                fused front end's block rides on the merged view).
    #                Taken before the superstep's launch, outside
    #                dispatch_s, and kept out of the accounted total
    dispatch_s: float = 0.0
    fused_dispatch_s: float = 0.0
    admit_s: float = 0.0
    device_s: float = 0.0
    host_sync_s: float = 0.0
    collective_s: float = 0.0
    # per-kind split of collective_s (psum all-reduces vs all_to_all token /
    # sequence exchanges) — calibrated separately because their per-device
    # wire bytes differ; same view-into-device_s rule as the total
    collective_psum_s: float = 0.0
    collective_a2a_s: float = 0.0
    head_calls_total: int = 0
    model_evals_total: int = 0
    accepts_total: int = 0
    proposals_total: int = 0
    draft_points_total: int = 0  # branched speculation: points drafted (all branches)
    queue_latency_total: float = 0.0
    wall_time: float = 0.0
    dropped: int = 0  # rejected at admission (SLO admission control)
    slo_tracked: int = 0  # retired requests that carried a deadline
    slo_met_count: int = 0
    shard: Optional[int] = None  # worker's shard id (None: unsharded/merged)
    # health / backpressure signals (ROADMAP item 1's router contract),
    # refreshed by the worker at harvest boundaries and on health() calls:
    queue_depth: int = 0  # requests queued awaiting a slot (live)
    queue_depth_peak: int = 0  # high-watermark of the admission queue
    slot_occupancy: float = 0.0  # busy fraction of the slot batch (live)
    admission_pressure: float = 0.0  # live demand / round budget (live)
    draining: bool = False  # graceful drain: no new admissions accepted
    per_request: List[RequestMetrics] = dataclasses.field(default_factory=list)

    # every additive counter/timer `merged` sums across shards; wall_time is
    # deliberately absent (concurrent shards share one wall clock).  The
    # health signals have their own merge rules below: depth sums, the peak
    # and pressure take the worst shard, occupancy averages, draining is any.
    _MERGE_SUM = (
        "requests", "retired", "batches", "rounds_total", "supersteps",
        "overlapped_supersteps", "dispatch_s", "fused_dispatch_s", "admit_s",
        "device_s", "host_sync_s",
        "collective_s", "collective_psum_s", "collective_a2a_s",
        "head_calls_total",
        "model_evals_total", "accepts_total", "proposals_total",
        "draft_points_total",
        "queue_latency_total", "dropped", "slo_tracked", "slo_met_count",
        "queue_depth",
    )

    @classmethod
    def merged(cls, shards: Sequence["EngineStats"],
               wall_time: Optional[float] = None) -> "EngineStats":
        """Cross-shard view: counters and timing components sum, per-request
        metrics concatenate, ``wall_time`` is the caller's single front-end
        wall (default: the max over shards — concurrent workers overlap, so
        their walls must not be added).

        Router-assigned request ids must be GLOBALLY unique: a duplicate
        rid across shards means two chains served the same request (or a
        router double-routed one) and every per-request aggregate here
        would silently double-count it — so it raises."""
        m = cls()
        for s in shards:
            for f in cls._MERGE_SUM:
                setattr(m, f, getattr(m, f) + getattr(s, f))
            m.per_request.extend(s.per_request)
        counts = Counter(rm.rid for rm in m.per_request)
        dupes = sorted(rid for rid, n in counts.items() if n > 1)
        if dupes:
            raise ValueError(
                f"duplicate request ids across merged shards: {dupes[:10]}"
                f"{' ...' if len(dupes) > 10 else ''} — router-assigned "
                "rids must be globally unique")
        m.wall_time = (
            wall_time if wall_time is not None
            else max((s.wall_time for s in shards), default=0.0))
        if shards:
            m.queue_depth_peak = max(s.queue_depth_peak for s in shards)
            m.admission_pressure = max(s.admission_pressure for s in shards)
            m.slot_occupancy = (
                sum(s.slot_occupancy for s in shards) / len(shards))
            m.draining = any(s.draining for s in shards)
        return m

    def observe(self, rm: RequestMetrics) -> None:
        self.retired += 1
        self.head_calls_total += rm.head_calls
        self.model_evals_total += rm.model_evals
        self.accepts_total += rm.accepts
        self.proposals_total += rm.proposals
        self.draft_points_total += rm.draft_points
        self.queue_latency_total += rm.queue_latency
        if rm.slo_met is not None:
            self.slo_tracked += 1
            self.slo_met_count += int(rm.slo_met)
        self.per_request.append(rm)

    def observe_drop(self, n: int = 1) -> None:
        """A request rejected at admission: its deadline was unmeetable."""
        self.dropped += n

    def parallel_depth_per_sample(self) -> float:
        return (self.rounds_total + self.head_calls_total) / max(self.requests, 1)

    def accept_rate(self) -> float:
        return self.accepts_total / max(self.proposals_total, 1)

    def mean_queue_latency(self) -> float:
        return self.queue_latency_total / max(self.retired, 1)

    def throughput(self) -> float:
        """Completed samples per second of engine wall time."""
        return self.retired / self.wall_time if self.wall_time > 0 else 0.0

    def slo_attainment(self) -> float:
        """Fraction of deadline-carrying requests that met their deadline.
        Admission-control drops count as misses (tracked but unmet)."""
        tracked = self.slo_tracked + self.dropped
        if tracked == 0:
            return 1.0
        return self.slo_met_count / tracked

    def mean_window(self) -> float:
        """Verified slots per fused round per chain (mean live theta)."""
        rounds = sum(m.rounds for m in self.per_request)
        return self.proposals_total / max(rounds, 1)

    def branch_accept_depth(self) -> float:
        """Mean accepted prefix per round over retired chains — the lane the
        branched-speculation benchmark keys its accept-depth ratios on."""
        rounds = sum(m.rounds for m in self.per_request)
        return self.accepts_total / max(rounds, 1)

    def wasted_draft_frac(self) -> float:
        """Drafted verification points that never committed, as a fraction
        of all drafted points (equals ``1 - accept_rate`` at one branch)."""
        if self.draft_points_total <= 0:
            return 0.0
        return 1.0 - self.accepts_total / self.draft_points_total

    def latency_percentiles(self, qs=(50, 95, 99)) -> dict:
        """Nearest-rank percentiles of queue and completion (submit ->
        retire) latency over retired requests — the open-loop traffic
        numbers.

        Explicit edge handling: an empty engine reports zeros, a single
        sample IS every percentile, and the nearest-rank
        ``rank = ceil(q * n / 100)`` is clamped to [1, n] so q <= 0 or
        q >= 100 can never index out of range."""

        def pcts(values):
            if not values:
                return {f"p{q}": 0.0 for q in qs}
            ordered = sorted(values)
            n = len(ordered)
            out = {}
            for q in qs:
                rank = min(max(math.ceil(q * n / 100.0), 1), n)
                out[f"p{q}"] = ordered[rank - 1]
            return out

        return {
            "queue": pcts([m.queue_latency for m in self.per_request]),
            "completion": pcts([m.latency for m in self.per_request]),
        }

    def mean_parallel_depth(self) -> float:
        """Mean per-request sequential model-call depth (rounds + head calls)."""
        if not self.per_request:
            return 0.0
        return sum(m.parallel_depth for m in self.per_request) / len(self.per_request)

    def timing_breakdown(self) -> dict:
        """Dispatch / device-wait / host-sync split of the engine's wall
        time, absolute and as fractions — the superstep win is the
        host_sync + dispatch fraction shrinking as rounds_per_sync grows.

        Fractions are always well-defined: the denominator is the LARGER of
        the recorded wall and the accounted component total.  Under the
        double-buffered overlap (and in merged cross-shard views, where
        components sum over concurrent workers) the components can exceed
        the single wall clock — dividing by the wall alone would report
        fractions summing past 1.  When no serve() wall has been recorded
        at all (e.g. a step()-driven open loop, where the driver owns the
        wall clock) the accounted total is the denominator.

        ``collective_s`` (model-parallel all-reduce seconds) is reported
        against the SAME denominator but is deliberately NOT part of the
        accounted total: it is a calibrated view INTO the device's fused
        execution, already paid inside device_s/wall — adding it would
        double-count and shift the clamp."""
        accounted = (self.dispatch_s + self.fused_dispatch_s
                     + self.device_s + self.host_sync_s)
        denom = max(self.wall_time, accounted, 1e-12)
        return {
            "supersteps": self.supersteps,
            "rounds_per_superstep": self.rounds_total / max(self.supersteps, 1),
            "dispatch_s": self.dispatch_s,
            "fused_dispatch_s": self.fused_dispatch_s,
            "admit_s": self.admit_s,
            "device_s": self.device_s,
            "host_sync_s": self.host_sync_s,
            "collective_s": self.collective_s,
            "collective_psum_s": self.collective_psum_s,
            "collective_a2a_s": self.collective_a2a_s,
            "dispatch_frac": self.dispatch_s / denom,
            "fused_dispatch_frac": self.fused_dispatch_s / denom,
            "device_frac": self.device_s / denom,
            "host_sync_frac": self.host_sync_s / denom,
            "collective_frac": self.collective_s / denom,
            "collective_psum_frac": self.collective_psum_s / denom,
            "collective_a2a_frac": self.collective_a2a_s / denom,
            # branched speculation lanes (not time components — ride along
            # here so the bench's timing dump carries the branch economics)
            "branch_accept_depth": self.branch_accept_depth(),
            "wasted_draft_frac": self.wasted_draft_frac(),
        }

    def summary(self) -> dict:
        return {
            "requests": self.requests,
            "retired": self.retired,
            "dropped": self.dropped,
            "rounds_total": self.rounds_total,
            "supersteps": self.supersteps,
            "head_calls_total": self.head_calls_total,
            "model_evals_total": self.model_evals_total,
            "accept_rate": self.accept_rate(),
            "mean_window": self.mean_window(),
            "branch_accept_depth": self.branch_accept_depth(),
            "wasted_draft_frac": self.wasted_draft_frac(),
            "mean_parallel_depth": self.mean_parallel_depth(),
            "mean_queue_latency_s": self.mean_queue_latency(),
            "slo_attainment": self.slo_attainment(),
            "wall_time_s": self.wall_time,
            "throughput_rps": self.throughput(),
            "timing": self.timing_breakdown(),
            "health": {
                "queue_depth": self.queue_depth,
                "queue_depth_peak": self.queue_depth_peak,
                "slot_occupancy": self.slot_occupancy,
                "admission_pressure": self.admission_pressure,
                "draining": self.draining,
            },
        }
