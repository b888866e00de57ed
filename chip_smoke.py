#!/usr/bin/env python3
"""On-chip smoke run of the continuous ASD serving path (not a benchmark).

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # four chips: sharded + tensor-parallel

Serves ``paper-ldm-dit`` (DiT-XL class: 28 layers, d_model 1152, 16 heads,
1024 latent tokens x 16 channels) at full registry width with seeded random
weights, through the engine builder of ``repro.launch.serve``.  The zero
``out_proj`` of a fresh denoiser (x0_hat == 0: every proposal accepted) is
replaced by a seeded N(0, (OUT_PROJ_SCALE^2) / d_model) draw, so that GRS
both accepts and rejects (see OUT_PROJ_SCALE).

One chip (the default) runs three phases, each of which must pass:

  1. the compiled Pallas kernels against their references, on chip arrays
     at the model's event shape: ``grs`` vs ``core.grs``, and
     ``fused_gather`` / ``fused_verify_commit`` vs their ``ref.py``;
  2. the default lane: unpacked rounds, core GRS;
  3. the kernel lane: packed execution, the fused round kernels, the Pallas
     pack and GRS kernels, two rounds per sync.

Both lanes serve the same request keys; each must retire every request with
finite samples, a sequential depth below K, and at least one rejection.

``--four-chips`` runs only the multi-chip paths and what they are compared
with: the one-chip engine, ``ShardedASDEngine(shards=4, dispatch="fused")``
and tensor-parallel verify over four chips (``model_shards=4``).

The last line of standard output is one JSON object naming the device; it
is printed only when every phase passed.  The script exits non-zero, and
prints no such line, when JAX finds no TPU.  Times printed on the way are
those of this smoke run (compilation included where stated), not
measurements.
"""

from __future__ import annotations

import argparse
import gc
import json
import logging
import math
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

MODEL = "paper-ldm-dit"
SEED = 0
K = 100
THETA = 8
SLOTS = 4
REQUESTS = 8
# four chips: one slot per shard, one wave of requests
FOUR_CHIP_REQUESTS = 4
# Scale of the random x0 prediction.  At N(0, 1/d_model) (scale 1) a random
# denoiser's output jumps by O(1) per element between adjacent steps, and a
# proposal past the exact first point is accepted with probability about
# exp(-(B/sigma)^2 * 16384): in a reduced-width rehearsal every such point
# was rejected and depth was exactly K.  At 1/32 the early, noisy steps
# (small B/sigma) accept and the late ones reject.
OUT_PROJ_SCALE = 1.0 / 32

# Kernel-vs-reference tolerances (float32 throughout).  The kernels sum
# ||v||^2 and <v, xi> over D = 1024 * 16 = 16384 features in a different
# order than XLA's reduction; the float32 rounding of such a sum stays near
# sqrt(D) * 2^-24 ~ 1e-5 of its magnitude, so the sample z (which reads the
# sums only through the reflection coefficient) agrees to well inside 1e-4.
# The gathers are copies and must be exact.
Z_RTOL = Z_ATOL = 1e-4
# An accept decision compares log(u) with log_ratio; reduction-order noise
# can flip it only when the two lie closer than this.
ACCEPT_MARGIN = 1e-3

# Per-call denoiser outputs, four chips vs one.  The same program on
# another chip must give the same values; tensor parallelism changes the
# order of the row-parallel sums in bfloat16 (8 mantissa bits, eps 2^-8)
# across 28 layers, so its output is held to a relative L2 error of 5e-2.
DENOISER_REL_TOL = {"same program": 1e-6, "tensor-parallel": 5e-2}
# Depth (rounds + proposal calls) per request, four chips vs one: equal
# unless an accept decision sat within rounding noise of its threshold, so
# the mean depth may move by at most this fraction.
DEPTH_REL_TOL = 0.1


class SmokeFailure(Exception):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------- set-up


def serve_args(*extra: str, requests: int = REQUESTS, mesh: str = "1x1"):
    from repro.launch.serve import build_parser

    return build_parser().parse_args([
        "--model", MODEL, "--mesh", mesh, "--chains", str(requests),
        "--slots", str(SLOTS), "--theta", str(THETA), "--K", str(K),
        *extra])


def seeded_params(args):
    """The serve CLI's seeded weights, with ``out_proj`` drawn from
    N(0, OUT_PROJ_SCALE^2 / d_model) so that the denoiser output is not
    identically zero."""
    import jax

    from repro.launch.serve import _build

    mesh, dc, params = _build(args)
    out = params["out_proj"]
    draw = jax.random.normal(jax.random.PRNGKey(SEED + 1), out.shape, out.dtype)
    params["out_proj"] = jax.device_put(
        draw * (OUT_PROJ_SCALE / math.sqrt(dc.backbone.d_model)),
        out.sharding)
    return mesh, dc, params


def serve_lane(name, args, mesh, dc, params, requests):
    """Serve ``requests`` through the CLI's engine builder; returns the
    samples, the engine and the per-request depths."""
    import numpy as np

    from repro.launch.serve import build_engine, make_requests

    eng, _ = build_engine(args, mesh, dc, params)
    for r in make_requests(requests):
        eng.submit(r)
    t0 = time.perf_counter()
    eng.step()  # compiles, then runs one superstep
    t1 = time.perf_counter()
    out = eng.serve([])
    t2 = time.perf_counter()
    s = eng.stats
    depth = {m.rid: m.parallel_depth for m in s.per_request}
    log(f"{name}: first superstep (compile + 1 superstep) {t1 - t0:.1f}s, "
        f"rest of serve {t2 - t1:.1f}s, {s.rounds_total} rounds, "
        f"depth mean {np.mean(list(depth.values())):.1f} "
        f"max {max(depth.values(), default=0)} (K={K}), "
        f"accept rate {s.accept_rate():.3f} "
        f"({s.accepts_total}/{s.proposals_total})")
    check(sorted(out) == list(range(requests)) and s.retired == requests
          and not eng.dropped_rids,
          f"{name}: retired {sorted(out)} of {requests} requests")
    check(all(np.isfinite(v).all() for v in out.values()),
          f"{name}: non-finite samples")
    check(max(depth.values()) < K,
          f"{name}: sequential depth {max(depth.values())} not below K={K}")
    check(s.accepts_total < s.proposals_total,
          f"{name}: no proposal was rejected")
    return out, eng, depth


def donates(eng) -> bool:
    """Every worker of ``eng`` donates its slot state to the superstep."""
    return all(w._donate for w in getattr(eng, "workers", [eng]))


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} bytes"


# ------------------------------------------------------- one chip


def check_kernels(dc) -> None:
    """Compiled kernels vs their references at the model's event shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.grs import grs as core_grs
    from repro.kernels.grs.ops import grs as grs_kernel
    from repro.kernels.superstep import fused_gather, fused_verify_commit

    event = (dc.seq_len, dc.d_data)
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED + 2), 16))

    def normal(shape, scale=1.0):
        return scale * jax.random.normal(next(ks), shape, jnp.float32)

    def clear_of_threshold(u, sig, xi, mh, mt):
        """Rows whose accept decision lies further than ACCEPT_MARGIN from
        its threshold, from float64 sums: only those must agree exactly."""
        rows = len(u)
        v = (np.asarray(mh, np.float64) - np.asarray(mt)).reshape(rows, -1)
        x = np.asarray(xi, np.float64).reshape(rows, -1)
        s = np.asarray(sig, np.float64)
        log_ratio = -((v * x).sum(1) / s + (v * v).sum(1) / (2 * s * s))
        return np.abs(np.log(np.maximum(np.asarray(u, np.float64), 1e-20))
                      - np.minimum(log_ratio, 0.0)) > ACCEPT_MARGIN

    # GRS at the packed verify width: 32 rows = slots x theta
    R = SLOTS * THETA
    xi, mh = normal((R,) + event), normal((R,) + event)
    # per-row perturbation scales spanning certain accept .. certain reject
    scale = jnp.geomspace(1e-4, 1e-1, R)[:, None, None]
    m = mh + scale * normal((R,) + event)
    sig = jax.random.uniform(next(ks), (R,), minval=0.2, maxval=1.0)
    u = jax.random.uniform(next(ks), (R,))
    zk, ak = jax.jit(lambda *a: grs_kernel(*a, event_ndim=2))(u, xi, mh, m, sig)
    zr, ar = jax.jit(lambda *a: core_grs(*a, event_ndim=2))(u, xi, mh, m, sig)
    sure = clear_of_threshold(u, sig, xi, mh, m)
    ak, ar = np.asarray(ak), np.asarray(ar)
    check((ak == ar)[sure].all(), "grs kernel accept decisions differ")
    check(0 < ar.sum() < R, f"grs check drew {ar.sum()}/{R} accepts; "
          "the inputs must exercise both branches")
    dz = np.abs(np.asarray(zk) - np.asarray(zr))[sure].max()
    check(np.allclose(np.asarray(zk)[sure], np.asarray(zr)[sure],
                      rtol=Z_RTOL, atol=Z_ATOL), f"grs kernel z off by {dz}")
    log(f"grs kernel vs core.grs: {R} rows x {event}, "
        f"{int(ar.sum())} accepts, max |dz| {dz:.3g}, "
        f"{int((~sure).sum())} rows within {ACCEPT_MARGIN} of the threshold")

    # the fused round pair at 16 slots x theta 32 = 512 window rows
    N = M = 512
    y, xi, mh = normal((N,) + event), normal((N,) + event), normal((N,) + event)
    scal = normal((N, 5))
    idx = jax.random.randint(next(ks), (M,), 0, N, jnp.int32)
    got = jax.jit(lambda *a: fused_gather(*a, impl="kernel"))(
        y, xi, mh, scal, idx)
    ref = jax.jit(lambda *a: fused_gather(*a, impl="ref"))(
        y, xi, mh, scal, idx)
    for g_, r_ in zip(got, ref):
        check(np.array_equal(np.asarray(g_), np.asarray(r_)),
              "fused_gather kernel differs from its reference")
    log(f"fused_gather kernel vs ref: N={N} window rows x {event}, M={M} "
        "packed rows, exact")

    yp, gp, xip = got[0], normal((M,) + event), got[1]
    A = jax.random.uniform(next(ks), (M,), minval=0.9, maxval=1.0)
    B = jnp.geomspace(1e-4, 1e-1, M)
    mhp = A[:, None, None] * yp + B[:, None, None] * (
        gp + normal((M,) + event, 0.1))
    u = jax.random.uniform(next(ks), (M,))
    sig = jax.random.uniform(next(ks), (M,), minval=0.2, maxval=1.0)
    # unique in-range destinations, and a tail of dropped padding lanes
    dest = jax.random.permutation(next(ks), N)[:M].at[-32:].set(N)
    args = (yp, gp, xip, mhp, A, B, u, sig, dest)
    zk, ak = jax.jit(lambda *a: fused_verify_commit(*a, N, impl="kernel"))(
        *args)
    zr, ar = jax.jit(lambda *a: fused_verify_commit(*a, N, impl="ref"))(*args)
    mt = A[:, None, None] * yp + B[:, None, None] * gp
    sure_p = clear_of_threshold(u, sig, xip, mhp, mt)
    d = np.asarray(dest)
    sure = np.ones((N,), bool)
    sure[d[d < N][~sure_p[d < N]]] = False
    ak, ar = np.asarray(ak), np.asarray(ar)
    check((ak == ar)[sure].all(),
          "fused_verify_commit accept decisions differ")
    check(0 < ar.sum() < (d < N).sum(),
          f"fused_verify_commit check drew {ar.sum()} accepts; the inputs "
          "must exercise both branches")
    dz = np.abs(np.asarray(zk) - np.asarray(zr))[sure].max()
    check(np.allclose(np.asarray(zk)[sure], np.asarray(zr)[sure],
                      rtol=Z_RTOL, atol=Z_ATOL),
          f"fused_verify_commit z off by {dz}")
    log(f"fused_verify_commit kernel vs ref: M={M} rows into {N} x {event}, "
        f"{int(ar.sum())} accepts, max |dz| {dz:.3g}, "
        f"{int((~sure).sum())} rows within {ACCEPT_MARGIN} of the threshold")


def one_chip() -> None:
    import numpy as np

    from repro.kernels._backend import resolve_interpret

    check(resolve_interpret() is False,
          "Pallas kernels would run in interpret mode on this backend")
    t0 = time.perf_counter()
    args = serve_args()
    mesh, dc, params = seeded_params(args)
    log(f"{MODEL}: {dc.backbone.n_layers} layers, d_model "
        f"{dc.backbone.d_model}, {dc.backbone.n_heads} heads, event "
        f"{dc.seq_len}x{dc.d_data}; weights in {time.perf_counter() - t0:.1f}s")

    check_kernels(dc)

    out_a, eng_a, depth_a = serve_lane("default lane (unpacked, core GRS)",
                                       args, mesh, dc, params, REQUESTS)
    check(donates(eng_a), "the default lane did not donate its slot state")
    del eng_a
    gc.collect()
    kernel_args = serve_args(
        "--execution", "packed", "--round-impl", "fused",
        "--pack-impl", "kernel", "--grs-impl", "kernel",
        "--rounds-per-sync", "2")
    out_b, eng_b, depth_b = serve_lane(
        "kernel lane (packed, fused round kernels, R=2)", kernel_args,
        mesh, dc, params, REQUESTS)
    check(donates(eng_b), "the kernel lane did not donate its slot state")
    same = sum(depth_a[r] == depth_b[r] for r in depth_a)
    dmax = max(float(np.abs(out_a[r] - out_b[r]).max()) for r in out_a)
    log(f"lanes on the same keys: {same}/{REQUESTS} requests at equal "
        f"depth, max |sample a - sample b| {dmax:.3g}")


# ----------------------------------------------------- four chips


def four_chips() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.distributed.sharding import mp_param_pspecs, shardings_from_pspecs
    from repro.launch.serve import make_mesh
    from repro.models.diffusion import denoiser_init, make_ddpm_model_fn

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 chips, JAX sees "
          f"{len(devices)}")
    n = FOUR_CHIP_REQUESTS
    args = serve_args(requests=n)
    mesh, dc, params = seeded_params(args)  # on device 0

    # per-call denoiser outputs on the same points
    ks = jax.random.split(jax.random.PRNGKey(SEED + 3), 2)
    t = jnp.linspace(0.0, K - 1, THETA + 1)
    y = jax.random.normal(ks[0], (THETA + 1, dc.seq_len, dc.d_data))
    call = jax.jit(lambda p, t, y: make_ddpm_model_fn(p, dc)(t, y))
    ref = np.asarray(call(params, t, y))

    def rel_err(x):
        return float(np.linalg.norm(np.asarray(x) - ref) / np.linalg.norm(ref))

    errs = [rel_err(call(*jax.device_put((params, t, y), d)))
            for d in devices[:4]]
    log(f"denoiser, same program on chips 0-3: relative L2 error "
        f"{', '.join(f'{e:.3g}' for e in errs)}")
    check(max(errs) <= DENOISER_REL_TOL["same program"],
          f"denoiser differs across chips: {errs}")
    tp_mesh = Mesh(np.asarray(devices[:4]), ("model",))
    boxed = jax.eval_shape(lambda k: denoiser_init(k, dc), jax.random.PRNGKey(0))
    specs = mp_param_pspecs(boxed, tp_mesh, tensor=True)
    tp_call = jax.jit(jax.shard_map(
        lambda p, t, y: make_ddpm_model_fn(p, dc, tp_axis="model")(t, y),
        mesh=tp_mesh, in_specs=(specs, P(), P()), out_specs=P(),
        check_vma=False))
    tp_err = rel_err(tp_call(
        jax.device_put(params, shardings_from_pspecs(tp_mesh, specs)), t, y))
    log(f"denoiser, tensor-parallel over 4 chips vs one: relative L2 error "
        f"{tp_err:.3g}")
    check(tp_err <= DENOISER_REL_TOL["tensor-parallel"],
          f"tensor-parallel denoiser off by {tp_err}")

    out_1, eng, depth_1 = serve_lane("one chip", args, mesh, dc, params, n)
    del eng
    gc.collect()

    def agree(name, out, depth):
        check(sorted(out) == sorted(out_1), f"{name}: retired set differs")
        m1 = np.mean([depth_1[r] for r in out_1])
        m4 = np.mean([depth[r] for r in out_1])
        same = sum(depth[r] == depth_1[r] for r in out_1)
        dmax = max(float(np.abs(out[r] - out_1[r]).max()) for r in out_1)
        log(f"{name} vs one chip: {same}/{n} requests at equal depth, mean "
            f"depth {m4:.2f} vs {m1:.2f}, max |sample diff| {dmax:.3g}")
        check(abs(m4 - m1) <= DEPTH_REL_TOL * m1,
              f"{name}: mean depth {m4} vs {m1}")

    sh_args = serve_args("--shards", "4", "--dispatch", "fused",
                         requests=n, mesh="4x1")
    out_s, eng, depth_s = serve_lane(
        "4 shards, fused dispatch", sh_args, make_mesh("4x1"), dc, params, n)
    placed = sorted({s.device.id for s in eng._states.a.addressable_shards})
    log(f"4 shards: slot state on devices {placed}, "
        f"shard block {eng._states.a.addressable_shards[0].data.shape}")
    check(len(placed) == 4, f"shard states sit on devices {placed}")
    check(donates(eng), "the sharded engine did not donate its slot state")
    agree("4 shards", out_s, depth_s)
    del eng
    gc.collect()

    tp_args = serve_args("--model-shards", "4", requests=n, mesh="1x4")
    out_t, eng, depth_t = serve_lane(
        "tensor-parallel verify, 4 chips", tp_args, mesh, dc, params, n)
    w = eng.workers[0]
    wq = w._params["decoder"]
    wq = next(leaf for path, leaf in jax.tree_util.tree_flatten_with_path(wq)[0]
              if getattr(path[-1], "key", None) == "wq")
    shard_shapes = {s.data.shape for s in wq.addressable_shards}
    tp_devs = sorted({s.device.id for s in wq.addressable_shards})
    log(f"tensor-parallel: wq {wq.shape} as {sorted(shard_shapes)} on "
        f"devices {tp_devs}")
    check(len(tp_devs) == 4 and all(
        math.prod(s) * 4 == math.prod(wq.shape) for s in shard_shapes),
        f"tensor-parallel weights are not split over 4 chips: "
        f"{shard_shapes} on {tp_devs}")
    check(donates(eng), "the tensor-parallel engine did not donate")
    agree("tensor-parallel", out_t, depth_t)
    for d in devices[:4]:
        log(f"peak memory on {d.id}: {peak_bytes(d)}")


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip paths (sharded serving and "
                         "tensor-parallel verify) against the one-chip engine")
    opts = ap.parse_args(argv)
    logging.basicConfig(format="%(levelname)s %(name)s: %(message)s",
                        level=logging.WARNING)

    import jax

    from repro.launch.serve import configure_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 2
    log(f"compile cache: {configure_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x {len(jax.devices())}; "
        "smoke run, not a benchmark")
    t0 = time.perf_counter()
    try:
        four_chips() if opts.four_chips else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"peak memory on device 0: {peak_bytes(dev)}; total "
        f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
