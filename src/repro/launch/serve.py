"""Production pjit ASD server: batched diffusion sampling on a mesh.

    PYTHONPATH=src python -m repro.launch.serve --chains 8 --theta 8

``--mesh DATAxMODEL`` defaults to every visible device on the data axis
(``1x1`` on one chip); a mesh larger than the device count is refused.
Off the chip, ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
simulates devices.  Compiled programs persist in JAX's compilation cache:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` at the checkout
root (``configure_compile_cache``).

Two serving modes:

  --engine fused       one fused batched-ASD program (asd_sample under vmap):
                       chains shard over (pod, data), denoiser weights over
                       model — each launch runs to its slowest chain.
  --engine continuous  the continuous-batching engine: a slot batch of
                       resumable ``ASDChainState``s sharded over (pod, data)
                       is driven one speculation round at a time; finished
                       chains retire at round boundaries and their slots are
                       refilled from the request queue (repro/serving).

Both are the TPU-native form of the paper's multi-GPU parallel verification:
the per-round model call is a (slots*theta)-point forward, data-parallel
over the mesh.

Speculation control and scheduling are pluggable:

  --theta-controller static|aimd|accept-rate   per-chain live window
  --num-branches 2                             branched speculation: roll B
                                               exchangeable draft branches
                                               per round and commit the one
                                               with the longest accepted
                                               prefix (1 = bit-identical to
                                               single-draft)
  --branch-controller static|gain              per-chain live branch count
  --policy fcfs|priority|serr|deadline|budget  slot admission policy
  --grs-impl core|kernel                       verifier backend (the Pallas
                                               GRS kernel runs interpret-mode
                                               off-TPU)

Packed ragged verification (repro/serving/packing): gather only the LIVE
verification points across slots into one fixed budget-shaped model call, so
adaptive windows save wall-clock, not just counted work:

  --execution packed --round-budget 96         e.g. ~0.85 * slots * theta
  --allocator proportional|waterfill|priority  budget split across slots
  --pack-impl ref|kernel                       ragged gather/scatter backend
  --round-impl packed|fused                    fused: ONE kernel each for the
                                               round's gather and
                                               verify/commit sides, with the
                                               budget tier carried as DATA
                                               (one executable per R)

Device-resident supersteps: fuse R speculation rounds per dispatch (the
slot-state pytree is donated to XLA and updated in place; the host only
syncs retire flags at superstep boundaries, off the critical path while
no request waits for a slot):

  --rounds-per-sync 4      fixed superstep length
  --rounds-per-sync auto   accept-rate-adaptive R on a power-of-two ladder

Sharded serving (repro/serving/sharded): shard-local workers behind a
request router — each shard owns a slot sub-batch pinned to its own device,
its own admission queue, and its own verification budget, so packed gathers
never cross shards (and on a pod, never cross hosts):

  --shards 4                                   shard-local workers
  --router least-loaded|round-robin|deadline   request routing policy
  --dispatch per-shard|fused                   per-shard: one program per
                                               worker (per-shard budget
                                               tiers); fused: ONE shard_map
                                               program over a slots mesh
                                               (scales across devices)
  --round-budget auto                          per-shard budget tiers,
                                               rebalanced from live demand
  --overcommit 1.5                             BudgetAware admits up to
                                               1.5x the budget's demand

Model-parallel shards (model parallelism INSIDE each shard): every shard
owns an mp-device model group — a row of ``serving_mesh(shards, mp)`` —
and its verify call runs sharded over the group's "model" axis, with every
collective inside the superstep program so the boundary still costs one
dispatch.  Three modes share the axis:

  --model-shards 2      tensor parallelism: QKV/output projections and the
                        dense FFN shard (``tp_param_pspecs``); psum
                        all-reduces per layer.  1 = replicated,
                        bit-identical to the existing engine.
  --expert-parallel     expert parallelism for MoE backbones: the (E,d,ff)
                        expert stacks shard over the group (each device
                        owns E/mp experts, ``mp_param_pspecs(expert=True)``)
                        and tokens reach their expert owners via two
                        all_to_all exchanges per MoE layer.  Composes with
                        either mode above/below; needs a model group
                        (--model-shards > 1 or --seq-shards > 1).
  --seq-shards 2        Ulysses sequence parallelism: weights replicate,
                        the residual stream is sequence-sharded through the
                        stack, and attention trades sequence for heads
                        (all_to_all) around its core — activation memory
                        and attention FLOPs at 1/mp for long-context
                        backbones.  Mutually exclusive with
                        --model-shards > 1 (both consume the head axis);
                        requires attn-only groups, heads % sp == 0 and
                        seq_len % sp == 0.

Observability (repro/serving/obs): structured tracing, live metrics, and
profiling are opt-in and cost nothing when off:

  --metrics-port 9100      serve /metrics (Prometheus text), /metrics.json,
                           and /healthz (503 under drain/backpressure) on a
                           daemon thread; 0 binds an ephemeral port
  --trace-out t.json       record request-lifecycle + superstep boundary
                           spans into a ring buffer and export Chrome
                           trace-event JSON (load in Perfetto / about:tracing)
  --trace-capacity 65536   ring size (drop-oldest beyond it)
  --profile-supersteps 8   bracket N warm supersteps in jax.profiler.trace
  --profile-dir results/profile
  --log-level info         repro.serving.* logger threshold
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import pathlib
import time
from types import SimpleNamespace
import urllib.error
import urllib.request

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import get_denoiser_config
from repro.core.asd import asd_sample_batched
from repro.core.controller import (
    BRANCH_CONTROLLERS,
    CONTROLLERS,
    make_branch_controller,
    make_controller,
)
from repro.core.schedules import ddpm as ddpm_schedule
from repro.distributed.sharding import (
    batch_pspec,
    chain_state_shardings,
    mp_param_pspecs,
    param_pspecs,
    serving_mesh,
    shard_placements,
    shardings_from_pspecs,
)
from repro.models.diffusion import (
    denoiser_init,
    make_ddpm_model_fn,
    mp_collective_payloads,
    sp_compatible,
)
from repro.nn.param import unbox
from repro.serving.engine import ContinuousASDEngine, Request
from repro.serving.obs import (
    MetricsRegistry,
    MetricsServer,
    TraceRecorder,
    instrument_engine,
)
from repro.serving.packing import ALLOCATORS, make_allocator
from repro.serving.router import ROUTERS, make_router
from repro.serving.scheduler import POLICIES, make_policy
from repro.serving.sharded import ShardedASDEngine

log = logging.getLogger("repro.serving.serve")


# the checkout root: src/repro/launch/serve.py -> three levels up
_CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself.
    Otherwise the cache goes to ``.jax_cache/`` at the checkout root: a
    fixed path, because the path is part of what a later run must find.
    Called by the entry points only, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_mesh(spec: str | None) -> Mesh:
    """The serving mesh from ``--mesh`` (DATAxMODEL or PODxDATAxMODEL).

    ``None`` puts every visible device on the data axis.  A mesh that needs
    more devices than JAX sees is refused with the device count and
    platform, instead of failing inside a reshape."""
    devices = jax.devices()
    spec = spec or f"{len(devices)}x1"
    dims = tuple(int(x) for x in spec.split("x"))
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    need = int(np.prod(dims))
    if need > len(devices):
        raise SystemExit(
            f"--mesh {spec} needs {need} devices, but JAX sees "
            f"{len(devices)} {devices[0].platform} device(s); pass a mesh "
            f"of at most {len(devices)} devices, e.g. --mesh "
            f"{len(devices)}x1, or omit --mesh")
    if need < len(devices):
        log.warning("--mesh %s uses %d of the %d %s devices", spec, need,
                    len(devices), devices[0].platform)
    return Mesh(np.asarray(devices[:need]).reshape(dims), names)


def _build(args):
    mesh = make_mesh(args.mesh)

    dc = get_denoiser_config(args.model)
    boxed = jax.eval_shape(lambda k: denoiser_init(k, dc), jax.random.PRNGKey(0))
    shardings = shardings_from_pspecs(mesh, param_pspecs(boxed, mesh))
    params = jax.jit(
        lambda k: unbox(denoiser_init(k, dc)), out_shardings=shardings
    )(jax.random.PRNGKey(0))
    return mesh, dc, params


def run_fused(args):
    mesh, dc, params = _build(args)
    sched = ddpm_schedule(args.K)
    bshard = NamedSharding(mesh, batch_pspec(mesh))
    controller = make_controller(args.theta_controller)

    @jax.jit
    def sample(params, y0, key):
        model_fn = make_ddpm_model_fn(params, dc)
        res = asd_sample_batched(
            model_fn, sched, y0, key, args.theta, eager_head=True,
            noise_mode="counter", keep_trajectory=False,
            controller=controller,
        )
        return res.sample, res.rounds, res.head_calls

    y0 = jax.device_put(
        np.random.default_rng(0).standard_normal(
            (args.chains, dc.seq_len, dc.d_data), np.float32), bshard)
    t0 = time.perf_counter()
    out, rounds, heads = jax.block_until_ready(sample(params, y0, jax.random.PRNGKey(1)))
    dt = time.perf_counter() - t0
    depth = float(np.mean(np.asarray(rounds) + np.asarray(heads)))
    print(f"[fused] sampled {args.chains} chains (K={args.K}) in {dt:.1f}s "
          f"(includes compile); sequential depth {depth:.0f} "
          f"=> {args.K / depth:.1f}x algorithmic speedup")
    print(f"output {out.shape}, finite={bool(np.isfinite(np.asarray(out)).all())}")


def _profile_supersteps(eng, args, slots):
    """Bracket N warm supersteps in a ``jax.profiler`` trace.  A warm pool
    fills the slots and the first superstep runs BEFORE the bracket opens,
    so the profile shows steady-state dispatch/device overlap, not compile.
    The warm pool's results are discarded (its work does land in stats)."""
    for i in range(slots):
        eng.submit(Request(-1 - i, key=jax.random.PRNGKey(10**6 + i)))
    eng.step()  # compile + first dispatch, outside the profiled window
    with jax.profiler.trace(args.profile_dir):
        for _ in range(args.profile_supersteps):
            if not eng.step():
                break
    while eng.step():
        pass
    eng.drain_results()
    print(f"[profile] {args.profile_supersteps} warm supersteps -> "
          f"{args.profile_dir} (view with tensorboard or xprof)")


def make_requests(n: int) -> list:
    """The CLI's request set: request i carries key PRNGKey(1000 + i)."""
    return [Request(i, key=jax.random.PRNGKey(1000 + i)) for i in range(n)]


def build_engine(args, mesh, dc, params):
    """The continuous serving engine the CLI flags describe, over ``params``
    (a pytree from ``_build``).  Returns ``(engine, layout)``; ``layout``
    carries the resolved slot, budget and model-parallel settings."""
    sched = ddpm_schedule(args.K)
    batch_world = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                               if a in ("pod", "data")]))
    if args.slots:
        slots = args.slots
        if slots % batch_world:
            raise SystemExit(
                f"--slots {slots} must be a multiple of the mesh batch axes "
                f"(pod*data = {batch_world}) so the slot batch shards evenly")
    else:  # derive: ~half the request count, rounded up to shard evenly
        slots = max(args.chains // 2, batch_world)
        slots = ((slots + batch_world - 1) // batch_world) * batch_world

    if args.shards > 1 and slots % args.shards:
        raise SystemExit(
            f"--slots {slots} must divide evenly over --shards {args.shards}")
    # round_budget reaches the engine only on the packed path: the unpacked
    # engine must keep reporting budget == slots * theta so the budget-aware
    # admission policy's pressure signal stays truthful.  With shards the
    # budget is PER SHARD (each shard's round is one budget-shaped call over
    # its own slot sub-batch); "auto" turns on per-shard tier rebalancing.
    slots_local = slots // max(args.shards, 1)
    budget = None
    allocator = None
    if args.execution == "packed":
        if args.round_budget == "auto":
            budget = "auto"
        else:
            budget = (int(args.round_budget)
                      or slots_local * args.theta * args.num_branches)
        # a slot's max demand is theta * branches points: the waterfilling
        # level scan must be able to reach it
        allocator = make_allocator(
            args.allocator, theta_max=args.theta * args.num_branches)
    tracer = (TraceRecorder(capacity=args.trace_capacity)
              if args.trace_out else None)
    common = dict(
        schedule=sched,
        event_shape=(dc.seq_len, dc.d_data),
        theta=args.theta,
        eager_head=True,
        noise_mode="counter",
        keep_trajectory=False,
        grs_impl=args.grs_impl,
        controller=make_controller(args.theta_controller),
        num_branches=args.num_branches,
        branch_controller=make_branch_controller(args.branch_controller),
        policy=make_policy(args.policy),
        execution=args.execution,
        round_budget=budget,
        allocator=allocator,
        pack_impl=args.pack_impl,
        round_impl=args.round_impl,
        rounds_per_sync=(args.rounds_per_sync if args.rounds_per_sync == "auto"
                         else int(args.rounds_per_sync)),
        overcommit=args.overcommit,
        tracer=tracer,
    )
    # model-parallel mode resolution: TP and SP both consume the head
    # axis, so they are mutually exclusive; EP rides whichever is on.
    mp, sp, ep = args.model_shards, args.seq_shards, args.expert_parallel
    if mp > 1 and sp > 1:
        raise SystemExit(
            "--model-shards > 1 and --seq-shards > 1 are mutually "
            "exclusive: both consume the attention head axis (TP's FFN "
            "psum would sum partial products of different token slices)")
    if sp > 1:
        ok, reason = sp_compatible(dc, sp)
        if not ok:
            raise SystemExit(f"--seq-shards {sp}: {reason}")
    mp_total = mp if mp > 1 else sp  # devices per model group
    if ep and mp_total <= 1:
        raise SystemExit(
            "--expert-parallel needs a model group to shard experts over: "
            "set --model-shards > 1 (or --seq-shards > 1)")
    if args.shards > 1 or mp_total > 1:
        # shard-local workers: each pinned to its own device of the mesh's
        # device set (round-robin when shards > devices), requests routed
        # above the compute layer — no cross-shard gathers by construction.
        # A model group (mp_total > 1) widens each shard to mp_total
        # devices and runs the verify model-parallel inside it.
        factory = lambda p, cond: make_ddpm_model_fn(p, dc)
        eng_devices = shard_placements(args.shards, list(mesh.devices.flat))
        tp_kwargs = {}
        if mp_total > 1:
            tp_mesh = serving_mesh(args.shards, mp_total)  # validates devices
            boxed = jax.eval_shape(
                lambda k: denoiser_init(k, dc), jax.random.PRNGKey(0))
            specs = mp_param_pspecs(boxed, tp_mesh,
                                    tensor=mp > 1, expert=ep)
            tp_kwargs = dict(
                param_specs=specs,
                collective_payloads=mp_collective_payloads(
                    params, specs, dc, mp_size=mp_total, sp_size=sp))
            factory = lambda p, cond: make_ddpm_model_fn(
                p, dc,
                tp_axis="model" if mp > 1 else None,
                sp_axis="model" if sp > 1 else None,
                sp_size=sp,
                ep_axis="model" if ep else None)
            eng_devices = list(tp_mesh.devices.flat)
        eng = ShardedASDEngine(
            factory,
            params=params,
            num_slots=slots,
            shards=args.shards,
            model_shards=mp_total,
            router=make_router(args.router),
            dispatch=args.dispatch,
            devices=eng_devices,
            **tp_kwargs,
            **common,
        )
    else:
        eng = ContinuousASDEngine(
            lambda p, cond: make_ddpm_model_fn(p, dc),
            params=params,  # jit argument: keeps the mesh sharding of weights
            num_slots=slots,
            state_sharding=chain_state_shardings(mesh),
            **common,
        )
    layout = SimpleNamespace(slots=slots, slots_local=slots_local,
                             budget=budget, mp_total=mp_total, sp=sp, ep=ep,
                             tracer=tracer)
    return eng, layout


def run_continuous(args):
    mesh, dc, params = _build(args)
    eng, layout = build_engine(args, mesh, dc, params)
    slots, slots_local, budget = layout.slots, layout.slots_local, layout.budget
    mp_total, sp, ep, tracer = layout.mp_total, layout.sp, layout.ep, layout.tracer
    server = None
    if args.metrics_port >= 0:
        registry = MetricsRegistry()
        instrument_engine(registry, eng)
        server = MetricsServer(registry, health_fn=eng.healthz,
                               port=args.metrics_port)
        server.start()
        print(f"[metrics] serving /metrics and /healthz at {server.url}")
    if args.profile_supersteps > 0:
        _profile_supersteps(eng, args, slots)
    reqs = make_requests(args.chains)
    t0 = time.perf_counter()
    out = eng.serve(reqs)
    dt = time.perf_counter() - t0
    s = eng.stats
    exec_desc = (f"packed B={budget}/{slots_local * args.theta} "
                 f"alloc={args.allocator}"
                 if args.execution == "packed" else "unpacked")
    shard_desc = (f", shards={args.shards} router={args.router}"
                  if args.shards > 1 else "")
    if mp_total > 1:
        shard_desc += f", mp={mp_total}"
        if sp > 1:
            shard_desc += f" (sequence-parallel)"
        if ep:
            shard_desc += f" (expert-parallel)"
    print(f"[continuous] served {s.retired} requests on {slots} slots "
          f"({exec_desc}{shard_desc}, K={args.K}, policy={args.policy}, "
          f"controller={args.theta_controller}, grs={args.grs_impl}, "
          f"R={args.rounds_per_sync}) "
          f"in {dt:.1f}s (includes compile): "
          f"{s.rounds_total} fused rounds in {s.supersteps} supersteps, "
          f"accept rate {s.accept_rate():.2f}, "
          f"mean live window {s.mean_window():.1f}/{args.theta}, "
          + (f"branch depth {s.branch_accept_depth():.2f} "
             f"(waste {s.wasted_draft_frac():.2f}, B={args.num_branches}), "
             if args.num_branches > 1 else "")
          +
          f"mean queue latency {s.mean_queue_latency()*1e3:.0f}ms, "
          f"SLO attainment {s.slo_attainment():.2f}, "
          f"{s.throughput():.2f} samples/s")
    if args.shards > 1 or mp_total > 1:
        if args.dispatch == "fused":
            rows = np.asarray(eng._mesh.devices).reshape(eng.num_shards, -1)
            devs = [list(r) for r in rows]
        elif mp_total > 1:
            devs = [list(w._model_mesh.devices.flat) for w in eng.workers]
        else:
            devs = [w.device for w in eng.workers]
        for w, n, dev in zip(eng.workers, eng.routed_counts, devs):
            log.info("shard %d: %d routed, %d retired, %d rounds, "
                     "budget %s, device %s", w.shard_id, n, w.stats.retired,
                     w.stats.rounds_total, w.round_budget, dev)
    if mp_total > 1:
        tb = s.timing_breakdown()
        print(f"  collectives: {tb['collective_s']*1e3:.1f}ms "
              f"({tb['collective_frac']:.1%} of wall, calibrated; "
              f"psum {tb['collective_psum_s']*1e3:.1f}ms, "
              f"all_to_all {tb['collective_a2a_s']*1e3:.1f}ms)")
    sample = next(iter(out.values()))
    print(f"output {sample.shape} per request, "
          f"finite={bool(np.isfinite(sample).all())}")
    if server is not None:
        # self-scrape before shutdown: proves the endpoints answer with the
        # numbers the engine just produced (and gives CI one line to grep)
        body = urllib.request.urlopen(
            server.url + "/metrics", timeout=5).read().decode()
        try:
            hz_body = urllib.request.urlopen(
                server.url + "/healthz", timeout=5).read()
        except urllib.error.HTTPError as e:  # 503 carries the payload too
            hz_body = e.read()
        hz = json.loads(hz_body)
        n_samples = sum(1 for ln in body.splitlines()
                        if ln and not ln.startswith("#"))
        print(f"[metrics] scraped {n_samples} samples from "
              f"{server.url}/metrics; /healthz status={hz['status']}")
        server.stop()
    if tracer is not None:
        doc = tracer.export_chrome_trace(args.trace_out)
        print(f"[trace] {len(doc['traceEvents'])} events "
              f"({doc['droppedEvents']} dropped) -> {args.trace_out} "
              f"(load in Perfetto / chrome://tracing)")


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="paper-diffusion-policy")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL (or PODxDATAxMODEL) device mesh; "
                         "default: every visible device on the data axis")
    ap.add_argument("--engine", default="continuous",
                    choices=("continuous", "fused"))
    ap.add_argument("--chains", type=int, default=8)
    ap.add_argument("--slots", type=int, default=0,
                    help="continuous engine slots (default: ~chains/2, "
                         "rounded up to a multiple of the mesh batch axes)")
    ap.add_argument("--theta", type=int, default=8,
                    help="speculation window cap theta_max (buffers are "
                         "shaped by it; the controller sets the live window)")
    ap.add_argument("--K", type=int, default=100)
    ap.add_argument("--theta-controller", default="static",
                    choices=sorted(CONTROLLERS),
                    help="per-chain speculation-window controller")
    ap.add_argument("--num-branches", type=int, default=1,
                    help="branched speculation cap B: draft branches rolled "
                         "per round per chain, committing the branch with "
                         "the longest accepted prefix (1 = single-draft, "
                         "bit-identical to the unbranched engine)")
    ap.add_argument("--branch-controller", default="static",
                    choices=sorted(BRANCH_CONTROLLERS),
                    help="per-chain live branch-count controller (b_live "
                         "<= --num-branches)")
    ap.add_argument("--policy", default="fcfs", choices=sorted(POLICIES),
                    help="continuous-engine admission policy")
    ap.add_argument("--grs-impl", default="core", choices=("core", "kernel"),
                    help="verifier backend: pure-jnp or the Pallas GRS "
                         "kernel (interpret-mode off-TPU)")
    ap.add_argument("--execution", default="unpacked",
                    choices=("unpacked", "packed"),
                    help="packed: gather only live verification points into "
                         "a fixed --round-budget model call per round")
    ap.add_argument("--round-budget", default="0",
                    help="packed verification points per round PER SHARD "
                         "(default: shard slots * theta, i.e. never "
                         'binding), or "auto" for live-demand budget tiers')
    ap.add_argument("--allocator", default="waterfill",
                    choices=sorted(ALLOCATORS),
                    help="packed budget split across slots")
    ap.add_argument("--pack-impl", default="ref", choices=("ref", "kernel"),
                    help="ragged gather/scatter backend (the Pallas pack "
                         "kernel runs interpret-mode off-TPU)")
    ap.add_argument("--round-impl", default="packed",
                    choices=("packed", "fused"),
                    help="packed-round body: per-phase programs, or the "
                         "fused kernel pair (repro.kernels.superstep) with "
                         "the budget tier as data — one executable per R, "
                         'composes round_budget="auto" with '
                         'dispatch="fused"')
    ap.add_argument("--rounds-per-sync", default="1",
                    help="speculation rounds fused per device dispatch: an "
                         "integer, or 'auto' to adapt to the observed "
                         "accept rate on a power-of-two ladder")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard-local serving workers; each owns "
                         "slots/shards lanes pinned to its own device, with "
                         "requests routed above the compute layer")
    ap.add_argument("--model-shards", type=int, default=1,
                    help="tensor parallelism inside each shard: devices per "
                         "model group (needs shards * model_shards devices; "
                         "QKV/output projections and FFN shard over the "
                         "group's 'model' axis, all-reduce inside the "
                         "superstep program)")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="shard MoE expert stacks over the model group "
                         "(each device owns E/mp experts; tokens reach "
                         "their expert owners via all_to_all inside the "
                         "superstep program).  Needs a model group: "
                         "--model-shards > 1 or --seq-shards > 1")
    ap.add_argument("--seq-shards", type=int, default=1,
                    help="Ulysses sequence parallelism inside each shard: "
                         "weights replicate, the residual stream is "
                         "sequence-sharded and attention trades sequence "
                         "for heads (all_to_all) around its core.  "
                         "Mutually exclusive with --model-shards > 1; "
                         "needs attn-only groups, heads %% sp == 0, "
                         "seq_len %% sp == 0")
    ap.add_argument("--router", default="least-loaded",
                    choices=sorted(ROUTERS),
                    help="sharded serving request router")
    ap.add_argument("--dispatch", default="per-shard",
                    choices=("per-shard", "fused"),
                    help="sharded execution: one program per worker (allows "
                         "per-shard budget tiers) or ONE fused shard_map "
                         "program over a slots mesh (one device per shard)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="BudgetAware admission multiplexing factor (>= 1): "
                         "admit until live demand reaches overcommit * "
                         "round_budget, trading window depth for occupancy")
    ap.add_argument("--metrics-port", type=int, default=-1,
                    help="serve /metrics (Prometheus text), /metrics.json, "
                         "and /healthz on 127.0.0.1:PORT (0 = ephemeral "
                         "port; default off)")
    ap.add_argument("--trace-out", default=None,
                    help="record request + superstep boundary spans and "
                         "export Chrome trace-event JSON to this path "
                         "(default off; zero device-side cost either way)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="trace ring-buffer capacity (drop-oldest beyond)")
    ap.add_argument("--profile-supersteps", type=int, default=0,
                    help="bracket N warm supersteps in jax.profiler.trace "
                         "before the timed serve (0 = off)")
    ap.add_argument("--profile-dir", default="results/profile",
                    help="--profile-supersteps output directory")
    ap.add_argument("--log-level", default="info",
                    choices=("debug", "info", "warning", "error"),
                    help="repro.serving.* logger threshold")
    return ap


def main():
    args = build_parser().parse_args()
    configure_compile_cache()
    # root stays at WARNING (jax's own loggers are chatty at DEBUG); the
    # flag governs the repro.serving.* hierarchy only
    logging.basicConfig(format="%(levelname)s %(name)s: %(message)s")
    logging.getLogger("repro.serving").setLevel(
        getattr(logging, args.log_level.upper()))
    if args.engine == "continuous":
        run_continuous(args)
    else:
        run_fused(args)


if __name__ == "__main__":
    main()
