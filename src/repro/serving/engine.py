"""ASD serving engines: batched diffusion-sampling requests.

Two engines share one request/metrics substrate:

``ASDServingEngine`` — the chunked static baseline.  Requests are padded into
fixed-size batches and each batch runs the *fused* batched-ASD program
(``asd_sample`` under vmap) to completion: every batch is paced by its
slowest chain and padded lanes burn compute.

``ContinuousASDEngine`` — the continuous-batching engine: ONE
``repro.serving.worker.ShardWorker`` (which owns the slot batch, the donated
superstep executables, the sync-packet harvest, and the admission queue)
plus the host boundary ``step()``.  The worker drives device-resident
SUPERSTEPS: each dispatch runs ``rounds_per_sync`` fused speculation rounds
under a ``lax.scan`` (chains that finish mid-superstep become masked no-ops,
bit-for-bit frozen), with the slot-state pytree DONATED to XLA so buffers
are reused in place instead of copied per round.  The host is a lazy
scheduler that only intervenes at superstep boundaries, and each
boundary is double-buffered (``repro.serving.worker.step_lanes``): it
admits and launches superstep s+1, then harvests superstep s's compact sync
packet (retire flags, counters, samples — one small transfer, no per-slot
peeks) while the device runs s+1.  The engine picks the order from its
queue: while a request waits for a slot, the boundary harvests s before it
admits and s+1 before it returns, so a slot freed by superstep s is
refilled at boundary s+1; with nobody waiting, ``step()`` returns with s+1
still in flight.  A chain that commits its final step retires at the next
boundary and its slot is refilled from the queue (FCFS, see
``repro.serving.scheduler``).  Each round is ONE fused (slots x theta)-point
verification forward — on a mesh it is pjit-sharded over the `data` axis
(see repro/launch/serve.py).

The continuous engine is parameterized on two pluggable axes:

  * a ``ThetaController`` (``repro.core.controller``) adapts each chain's
    live speculation window theta_live <= theta from its observed accepts,
    inside the jitted round (buffer shapes never change — no recompiles);
  * a ``SchedulingPolicy`` (``repro.serving.scheduler``) decides which
    queued request takes a freed slot (FCFS / priority / SJF-on-expected-
    rounds / earliest-deadline-first with SLO admission control).

Multi-shard serving — N workers behind a pluggable request router with
per-shard admission queues and budget rebalancing — lives in
``repro.serving.sharded.ShardedASDEngine``; with ``shards=1`` it is
bit-identical to this engine.

Both engines produce per-request ``RequestMetrics`` and an ``EngineStats``
aggregate (rounds, head calls, accept rate, queue latency, throughput,
SLO attainment).
"""

from __future__ import annotations

import logging
import time
from typing import Callable

log = logging.getLogger("repro.serving.engine")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.asd import asd_sample
from repro.core.schedules import Schedule
from repro.core.sequential import sequential_sample, init_y0
from repro.models.diffusion import DenoiserConfig
from repro.serving.metrics import EngineStats
from repro.serving.worker import Request, ShardWorker, step_lanes

__all__ = ["ASDServingEngine", "ContinuousASDEngine", "Request"]


# ---------------------------------------------------------------------------
# Continuous batching
# ---------------------------------------------------------------------------


class ContinuousASDEngine(ShardWorker):
    """Slot-based continuous-batching ASD server: one ``ShardWorker`` plus
    the double-buffered host boundary ``step()``.

    All constructor arguments are the worker's — see
    ``repro.serving.worker.ShardWorker`` for the full reference
    (controllers, policies, packed execution, budgets, supersteps,
    overcommit, auto budget tiers).
    """

    # -- request lifecycle --------------------------------------------------

    def submit(self, request: Request) -> None:
        if self.draining:
            raise RuntimeError(
                f"engine is draining: request {request.rid} rejected "
                "(begin_drain() closed the admission gate)")
        self.scheduler.submit(request, time.perf_counter())

    def step(self) -> bool:
        """One superstep boundary: admit, launch superstep s+1
        (``rounds_per_sync`` fused rounds over all slots) if there is work,
        and harvest superstep s while s+1 runs.

        The call returns with s+1 still in flight unless a request is left
        queued after this boundary's admissions; then it harvests s+1 too,
        so the next boundary refills the slots s+1 frees (and, with more
        requests queued than free slots, a boundary harvests s before it
        admits).  Chains retired in s are in ``drain_results()`` when the
        call returns.  Returns True while work is queued, in a slot or in
        flight: ``while eng.step(): pass`` ends with every result harvested
        and nothing in flight.
        """
        if not self.has_work():
            return False
        with self._span("step", superstep=self.stats.supersteps) as sp:
            sp.set(overlap=step_lanes([self._lane]))
        return self.has_work()

    def serve(self, requests: list[Request], key=None) -> dict[int, np.ndarray]:
        """Submit everything, ``step()`` until drained, return
        {rid: sample}."""
        if key is not None:
            self._key = key
        self.dropped_rids = []  # drops are reported per serve() wave
        log.debug("shard %d serve: %d requests submitted",
                  self.shard_id, len(requests))
        t0 = time.perf_counter()
        for r in requests:
            self.submit(r)
        while self.step():
            pass
        jax.block_until_ready(self._states.a)
        self.stats.wall_time += time.perf_counter() - t0
        self._refresh_health()
        log.info(
            "shard %d serve drained: %d retired (%d dropped) in %d "
            "supersteps", self.shard_id, self.stats.retired,
            self.stats.dropped, self.stats.supersteps)
        return self.drain_results()


# ---------------------------------------------------------------------------
# Chunked static baseline
# ---------------------------------------------------------------------------


class ASDServingEngine:
    """Batched exact-sampling server (chunked static batching baseline).

    mode: "asd" (speculative, parallel) or "ddpm" (sequential baseline).
    Every chunk is padded to ``batch_size`` and fused to run until its
    slowest chain finishes — the waste the continuous engine removes.
    """

    def __init__(
        self,
        params,
        dc: DenoiserConfig,
        schedule: Schedule,
        model_fn_factory: Callable,  # (params, dc, cond) -> model_fn
        theta: int = 8,
        batch_size: int = 8,
        mode: str = "asd",
        eager_head: bool = True,
    ):
        self.params = params
        self.dc = dc
        self.schedule = schedule
        self.theta = theta
        self.batch_size = batch_size
        self.mode = mode
        self.stats = EngineStats()
        ev_shape = (dc.seq_len, dc.d_data)

        def one_chain(cond, y0, key):
            model_fn = model_fn_factory(params, dc, cond if dc.d_cond else None)
            if mode == "asd":
                res = asd_sample(model_fn, schedule, y0, key, theta, eager_head)
                return res.sample, res.rounds, res.head_calls
            out, _ = sequential_sample(model_fn, schedule, y0, key)
            return out, jnp.asarray(schedule.K), jnp.asarray(schedule.K)

        def batch_fn(conds, keys):
            y0s = jnp.zeros((batch_size,) + ev_shape, jnp.float32)
            if schedule.y0_mode == "std_normal":
                y0s = jax.vmap(lambda k: init_y0(schedule, k, ev_shape))(
                    jax.random.split(keys[0], batch_size)
                )
            return jax.vmap(one_chain)(conds, y0s, keys)

        self._batch_fn = jax.jit(batch_fn)

    def submit_batch(self, requests: list[Request], key) -> dict[int, np.ndarray]:
        """Pads to batch_size, samples, returns {rid: sample}."""
        t0 = time.perf_counter()
        n = len(requests)
        assert n <= self.batch_size
        d_cond = self.dc.d_cond or 1
        conds = np.zeros((self.batch_size, d_cond), np.float32)
        for i, r in enumerate(requests):
            if r.cond is not None:
                conds[i] = r.cond
        keys = jax.random.split(key, self.batch_size)
        samples, rounds, heads = self._batch_fn(jnp.asarray(conds), keys)
        samples = jax.device_get(samples)
        self.stats.requests += n
        self.stats.batches += 1
        # the fused batch runs to its slowest chain: batch depth is the max
        self.stats.rounds_total += int(np.max(np.asarray(rounds)))
        self.stats.head_calls_total += int(np.max(np.asarray(heads)))
        self.stats.retired += n
        self.stats.wall_time += time.perf_counter() - t0
        return {r.rid: samples[i] for i, r in enumerate(requests)}

    def serve(self, requests: list[Request], key) -> dict[int, np.ndarray]:
        """Chunked static serving: pad the queue into fixed batches."""
        out = {}
        for i in range(0, len(requests), self.batch_size):
            chunk = requests[i : i + self.batch_size]
            key, sub = jax.random.split(key)
            out.update(self.submit_batch(chunk, sub))
        return out
