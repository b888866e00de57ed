"""The packed speculation round: plan -> pack -> verify -> commit.

One fused jitted program over a slot batch of ``ASDChainState``s that spends
at most ``budget`` verification points per round, however the live windows
are distributed:

  1. PLAN    (vmapped ``plan_round``): the dense per-slot proposal call plus
     the theta_max-shaped elementwise rollout — cheap, no parallel model
     work.  Demands are each slot's live points ``min(theta_live, K - a)``.
  2. PACK    the ``BudgetAllocator`` turns demands into grants; pack maps
     (``build_pack_maps``) lay the granted points out contiguously; the
     ragged gather (``kernels/pack``) moves y/xi/m_hat rows into the dense
     budget-shaped batch.  With ``eager_head`` each slot's head point rides
     in a fixed extra lane, so the packed call is (budget + slots) points.
  3. VERIFY  ONE model call over the packed points + ONE GRS pass — the only
     O(model) work in the round, and it is sized by the budget, not by
     slots * theta_max.  Small windows therefore free real compute.
  4. COMMIT  scatter z/accept back to theta_max-shaped per-slot buffers and
     run the shared ``commit_round`` with each slot's granted window as its
     effective window theta_r.

Exactness: a slot's grant depends only on pre-round state (it is
F_a-measurable, like theta_live itself — Lemma 13's filtration argument),
so a constrained round is just a round at a smaller live window.  When
``sum(demands) <= budget`` every grant equals its demand, theta_r equals
theta_live, and the packed round reproduces the unpacked ``asd_round``
bit for bit (asserted in tests/test_packed_round.py).

Compile-once: every shape in the program depends only on the static
``(budget, slots, theta_max)`` triple — grants, maps, and windows are data.

Two orthogonal knobs refine the round body:

  ``round_impl="fused"``  runs the non-model work through the fused Pallas
     round pair (``repro.kernels.superstep``): the ragged gather + all five
     scalar-window gathers collapse into ONE program, and the target mean,
     GRS pass, and both commit scatters collapse into ONE program — 7
     launches per round become 2 (+ plan/verify model calls).  The default
     ``pack_impl="ref"`` lane composes exactly the unfused primitives
     (``jnp.take``, ``core.grs.grs``, the drop-row scatter), so fused ≡
     packed bit for bit by construction.

  ``budget_data``  (budget-as-data) keeps ``budget`` as the STATIC pack
     shape (the cap — e.g. the auto-tier ladder top) while the tier actually
     granted this round arrives as a TRACED scalar: the allocator splits
     ``budget_data`` points, lanes past the granted total are dropped
     padding, and the executable no longer specializes per tier — budget
     tiers become data, exactly like the window mix.  Requires
     ``budget_data <= budget``; the verify call stays cap-shaped (the
     explicit tradeoff for one executable per R).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.asd import commit_round, plan_round
from repro.core.controller import (
    BranchController, StaticBranches, StaticTheta, ThetaController)
from repro.core.grs import bcast_right, grs
from repro.core.schedules import Schedule
from repro.core.verifier import leading_true_count
from repro.kernels.pack import gather_rows
from repro.serving.packing.plan import (
    build_branched_pack_maps, build_pack_maps)

_STATIC = StaticTheta()
_STATIC_B = StaticBranches()


def _gather_scalar(table: jax.Array, slot_id, step_id) -> jax.Array:
    """(S, theta) scalar table -> (B,) packed; cheap jnp fancy-gather."""
    return table[slot_id, step_id]


def _target_grs(A, B, y, g, u, xi, mh, sig, grs_impl: str, ev_ndim: int):
    """The unfused verify side of a packed round: target mean
    ``m = A y + B g`` per packed point, then one GRS pass."""
    m_tgt = bcast_right(A, ev_ndim + 1) * y + bcast_right(B, ev_ndim + 1) * g
    if grs_impl == "kernel":
        from repro.kernels.grs.ops import grs as grs_k

        return grs_k(u, xi, mh, m_tgt, sig, event_ndim=ev_ndim)
    return grs(u, xi, mh, m_tgt, sig, event_ndim=ev_ndim)


def packed_round(
    make_fn: Callable,
    params,
    schedule: Schedule,
    states,  # slot-batched ASDChainState (leading S axis on every leaf)
    conds: Optional[jax.Array],  # (S, d_cond) or None
    weights: jax.Array,  # (S,) f32 allocator priority weights
    *,
    theta: int,
    budget: int,
    allocator,
    eager_head: bool = True,
    noise_mode: str = "buffer",
    keep_trajectory: bool = False,
    grs_impl: str = "core",
    controller: ThetaController = _STATIC,
    pack_impl: str = "ref",
    round_impl: str = "packed",
    budget_data=None,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
):
    """One packed verification round over all slots; returns the new states.

    ``budget`` is the static pack shape; ``budget_data`` (optional traced
    scalar <= budget) is the tier the allocator actually splits this round.
    ``round_impl="fused"`` routes the gather and verify/commit through the
    fused kernel pair (``pack_impl`` picks its ref/kernel lane; ``grs_impl``
    only applies to the unfused body — fused runs GRS inside the kernel).
    ``num_branches`` B > 1 compiles the branched body (B draft branches per
    slot, branch-major pack maps, longest-accepted-prefix selection);
    ``num_branches == 1`` compiles this original body unchanged.
    """
    if num_branches > 1:
        return _branched_packed_round(
            make_fn, params, schedule, states, conds, weights,
            theta=theta, budget=budget, allocator=allocator,
            eager_head=eager_head, noise_mode=noise_mode,
            keep_trajectory=keep_trajectory, grs_impl=grs_impl,
            controller=controller, pack_impl=pack_impl,
            round_impl=round_impl, budget_data=budget_data,
            num_branches=num_branches, branch_controller=branch_controller,
        )
    K = schedule.K
    S = states.a.shape[0]
    ev_ndim = states.v_cache.ndim - 1

    # --- 1. plan: proposal call + rollout per slot (vmapped) ----------------
    def plan_one(st, cond):
        return plan_round(
            make_fn(params, cond), schedule, st, theta, eager_head,
            noise_mode, keep_trajectory,
        )

    if conds is None:
        plans = jax.vmap(lambda st: plan_one(st, None))(states)
    else:
        plans = jax.vmap(plan_one)(states, conds)

    # --- 2. pack: allocate the budget, build maps, gather live points -------
    with jax.named_scope("asd/pack"):
        active = states.a < K
        demand = jnp.where(active, plans.n_valid, 0).astype(jnp.int32)
        # budget-as-data: the allocator splits the (possibly traced) tier,
        # the maps below are built at the static cap — lanes past the granted
        # total are padding and drop at the commit scatter
        grants = allocator.allocate(
            demand, budget if budget_data is None else budget_data, weights)
        grants = jnp.minimum(grants, demand)  # contract guard: g <= d always
        # a fully-granted slot runs its true live window (head index
        # included); a trimmed slot runs the grant as its effective window
        # this round.  A zero grant (only possible when budget < #active
        # slots) is a safe stall: theta_r = 0 verifies nothing, commits
        # nothing, and advances nowhere.
        theta_r = jnp.where(grants >= demand, plans.theta_live, grants)
        maps = build_pack_maps(grants, budget)
        src_rows = jnp.where(  # gather side: padding lanes re-read row 0
            maps.valid, maps.slot_id * theta + maps.step_id, 0
        )

        def flat(x):  # (S, theta, *ev) -> (S*theta, *ev)
            return x.reshape((S * theta,) + x.shape[2:])

        if round_impl == "fused":
            from repro.kernels.superstep import fused_gather

            # the five per-point scalars ride as lanes of ONE (S*theta, 5)
            # table, so the fused gather moves event rows and scalars together
            scal_tbl = jnp.stack(
                [flat(plans.t_w1[:, :theta]), flat(plans.u_w),
                 flat(plans.A_w), flat(plans.B_w), flat(plans.sig_w)], axis=-1)
            y_pt, xi_pt, mh_pt, scal_pt = fused_gather(
                flat(plans.y_prev), flat(plans.xi_w), flat(plans.m_hats),
                scal_tbl, src_rows, impl=pack_impl)
            t_pt, u_pt, A_pt, B_pt, sig_pt = (
                scal_pt[:, i] for i in range(5))
        else:
            y_pt = gather_rows(flat(plans.y_prev), src_rows, impl=pack_impl)
            xi_pt = gather_rows(flat(plans.xi_w), src_rows, impl=pack_impl)
            mh_pt = gather_rows(flat(plans.m_hats), src_rows, impl=pack_impl)
            t_pt = _gather_scalar(plans.t_w1[:, :theta], maps.slot_id,
                                  maps.step_id)
            u_pt = _gather_scalar(plans.u_w, maps.slot_id, maps.step_id)
            A_pt = _gather_scalar(plans.A_w, maps.slot_id, maps.step_id)
            B_pt = _gather_scalar(plans.B_w, maps.slot_id, maps.step_id)
            sig_pt = _gather_scalar(plans.sig_w, maps.slot_id, maps.step_id)

        if eager_head:
            # one fixed head lane per slot: the point the chain lands on when
            # it accepts its whole effective window — next round's proposal
            # call
            y_head = jax.vmap(
                lambda yp, tr: jax.lax.dynamic_index_in_dim(
                    yp, tr - 1, axis=0, keepdims=False)
            )(plans.y_props, theta_r)
            t_head = jax.vmap(lambda tw, tr: tw[tr])(plans.t_w1, theta_r)
            ts_all = jnp.concatenate([t_pt, t_head], axis=0)
            ys_all = jnp.concatenate([y_pt, y_head], axis=0)
            conds_all = (
                None if conds is None
                else jnp.concatenate([conds[maps.slot_id], conds], axis=0)
            )
        else:
            ts_all, ys_all = t_pt, y_pt
            conds_all = None if conds is None else conds[maps.slot_id]

    # --- 3. verify: ONE budget-shaped model call + ONE GRS pass -------------
    with jax.named_scope("asd/verify"):
        if conds is None:
            g_all = make_fn(params, None)(ts_all, ys_all)
        else:
            g_all = jax.vmap(
                lambda t, y, c: make_fn(params, c)(t[None], y[None])[0]
            )(ts_all, ys_all, conds_all)
        if eager_head:
            g_pt, g_head = g_all[:budget], g_all[budget:]
        else:
            g_pt, g_head = g_all, None

    drop_rows = maps.row_id(theta)  # padding lanes -> the drop row
    if round_impl == "fused":
        from repro.kernels.superstep import fused_verify_commit

        # target mean + GRS + both commit scatters in ONE program
        with jax.named_scope("asd/grs"):
            z_tbl, acc_tbl = fused_verify_commit(
                y_pt, g_pt, xi_pt, mh_pt, A_pt, B_pt, u_pt, sig_pt,
                drop_rows, S * theta, impl=pack_impl)
        z_seg = z_tbl.reshape((S, theta) + z_tbl.shape[1:])
        acc_seg = acc_tbl.reshape(S, theta)
    else:
        with jax.named_scope("asd/grs"):
            z_pt, acc_pt = _target_grs(
                A_pt, B_pt, y_pt, g_pt, u_pt, xi_pt, mh_pt, sig_pt,
                grs_impl, ev_ndim)

        # --- 4. commit: scatter back and close each slot's round ------------
        from repro.kernels.pack import scatter_rows

        with jax.named_scope("asd/commit"):
            z_seg = scatter_rows(
                z_pt, drop_rows, S * theta, impl=pack_impl
            ).reshape((S, theta) + z_pt.shape[1:])
            acc_seg = (
                jnp.zeros((S * theta + 1,), bool)
                .at[drop_rows].set(acc_pt)[: S * theta]
                .reshape(S, theta)
            )

    def commit_one(st, plan, z, acc, gh, tr):
        return commit_round(
            schedule, st, plan, z, acc, tr, gh, theta,
            eager_head, keep_trajectory, controller,
        )

    if eager_head:
        return jax.vmap(commit_one)(states, plans, z_seg, acc_seg, g_head,
                                    theta_r)
    return jax.vmap(
        lambda st, plan, z, acc, tr: commit_one(st, plan, z, acc, None, tr)
    )(states, plans, z_seg, acc_seg, theta_r)


def _branched_packed_round(
    make_fn: Callable,
    params,
    schedule: Schedule,
    states,
    conds: Optional[jax.Array],
    weights: jax.Array,
    *,
    theta: int,
    budget: int,
    allocator,
    eager_head: bool,
    noise_mode: str,
    keep_trajectory: bool,
    grs_impl: str,
    controller: ThetaController,
    pack_impl: str,
    round_impl: str,
    budget_data,
    num_branches: int,
    branch_controller: BranchController,
):
    """The BRANCHED packed round: same plan -> pack -> verify -> commit
    pipeline with a branch axis through every stage.

    Demand is ``b_live * min(theta_live, K - a)`` per slot; a grant sheds
    BRANCHES before window width (a grant below one full window runs a
    single trimmed branch — exactly the unbranched trimmed round on the
    canonical stream; past one window, whole extra branches ride along and
    the longest accepted prefix wins at commit).  Pack maps are branch-major
    (``build_branched_pack_maps``) over the (S * B * theta)-row branched
    window stack; the same flat-table kernels (``kernels/pack`` gather /
    scatter, ``kernels/superstep`` fused pair) move the rows — only the
    table size and index arithmetic change.
    """
    K = schedule.K
    S = states.a.shape[0]
    NB = num_branches
    ev_ndim = states.v_cache.ndim - 1
    ev_shape = states.v_cache.shape[1:]

    # --- 1. plan: proposal + B-branch rollout per slot (vmapped) ------------
    def plan_one(st, cond):
        return plan_round(
            make_fn(params, cond), schedule, st, theta, eager_head,
            noise_mode, keep_trajectory, NB,
        )

    if conds is None:
        plans = jax.vmap(lambda st: plan_one(st, None))(states)
    else:
        plans = jax.vmap(plan_one)(states, conds)

    # --- 2. pack: branched demand, branch-shedding grant split, gather ------
    with jax.named_scope("asd/pack"):
        active = states.a < K
        n1 = plans.n_valid.astype(jnp.int32)  # live points PER BRANCH
        b_live = jnp.clip(states.b_live, 1, NB)
        demand = jnp.where(active, b_live * n1, 0).astype(jnp.int32)
        grants = allocator.allocate(
            demand, budget if budget_data is None else budget_data, weights)
        grants = jnp.minimum(grants, demand)
        covered = grants >= n1
        # branches granted: whole windows only (a partial extra branch cannot
        # beat branch 0's full prefix, so its points would be pure waste)
        b_r = jnp.clip(grants // jnp.maximum(n1, 1), 1, b_live)
        theta_r = jnp.where(covered, plans.theta_live, grants)
        pts1 = jnp.where(covered, n1, grants)  # == min(theta_r, K - a)
        maps = build_branched_pack_maps(pts1, b_r, budget)
        src_rows = jnp.where(
            maps.valid,
            (maps.slot_id * NB + maps.branch_id) * theta + maps.step_id, 0)

        def flatb(x):  # (S, B, theta, *ev) -> (S*B*theta, *ev)
            return x.reshape((S * NB * theta,) + x.shape[3:])

        def btile(x):  # per-slot (S, theta) scalar window -> (S, B, theta)
            return jnp.broadcast_to(x[:, None, :], (S, NB, theta))

        t_tbl = btile(plans.t_w1[:, :theta])
        A_tbl = btile(plans.A_w)
        B_tbl = btile(plans.B_w)
        sig_tbl = btile(plans.sig_w)

        if round_impl == "fused":
            from repro.kernels.superstep import fused_gather

            scal_tbl = jnp.stack(
                [flatb(t_tbl), flatb(plans.u_w_b), flatb(A_tbl), flatb(B_tbl),
                 flatb(sig_tbl)], axis=-1)
            y_pt, xi_pt, mh_pt, scal_pt = fused_gather(
                flatb(plans.y_prev_b), flatb(plans.xi_w_b),
                flatb(plans.m_hats_b), scal_tbl, src_rows, impl=pack_impl)
            t_pt, u_pt, A_pt, B_pt, sig_pt = (
                scal_pt[:, i] for i in range(5))
        else:
            y_pt = gather_rows(flatb(plans.y_prev_b), src_rows, impl=pack_impl)
            xi_pt = gather_rows(flatb(plans.xi_w_b), src_rows, impl=pack_impl)
            mh_pt = gather_rows(flatb(plans.m_hats_b), src_rows,
                                impl=pack_impl)
            t_pt = _gather_scalar(plans.t_w1[:, :theta], maps.slot_id,
                                  maps.step_id)
            u_pt = plans.u_w_b[maps.slot_id, maps.branch_id, maps.step_id]
            A_pt = _gather_scalar(plans.A_w, maps.slot_id, maps.step_id)
            B_pt = _gather_scalar(plans.B_w, maps.slot_id, maps.step_id)
            sig_pt = _gather_scalar(plans.sig_w, maps.slot_id, maps.step_id)

        if eager_head:
            # one head lane per (slot, branch): whichever branch wins a full
            # accept, its head evaluation is the next round's proposal call
            y_head = jax.vmap(
                lambda yp, tr: jax.vmap(
                    lambda ypb: jax.lax.dynamic_index_in_dim(
                        ypb, tr - 1, axis=0, keepdims=False))(yp)
            )(plans.y_props_b, theta_r)  # (S, B, *event)
            t_head = jax.vmap(lambda tw, tr: tw[tr])(plans.t_w1, theta_r)
            ts_all = jnp.concatenate([t_pt, jnp.repeat(t_head, NB)], axis=0)
            ys_all = jnp.concatenate(
                [y_pt, y_head.reshape((S * NB,) + ev_shape)], axis=0)
            conds_all = (
                None if conds is None
                else jnp.concatenate(
                    [conds[maps.slot_id], jnp.repeat(conds, NB, axis=0)],
                    axis=0)
            )
        else:
            ts_all, ys_all = t_pt, y_pt
            conds_all = None if conds is None else conds[maps.slot_id]

    # --- 3. verify: ONE budget-shaped model call + ONE GRS pass -------------
    with jax.named_scope("asd/verify"):
        if conds is None:
            g_all = make_fn(params, None)(ts_all, ys_all)
        else:
            g_all = jax.vmap(
                lambda t, y, c: make_fn(params, c)(t[None], y[None])[0]
            )(ts_all, ys_all, conds_all)
        if eager_head:
            g_pt = g_all[:budget]
            g_head = g_all[budget:].reshape((S, NB) + ev_shape)
        else:
            g_pt, g_head = g_all, None

    drop_rows = maps.row_id(NB, theta)
    if round_impl == "fused":
        from repro.kernels.superstep import fused_verify_commit

        with jax.named_scope("asd/grs"):
            z_tbl, acc_tbl = fused_verify_commit(
                y_pt, g_pt, xi_pt, mh_pt, A_pt, B_pt, u_pt, sig_pt,
                drop_rows, S * NB * theta, impl=pack_impl)
        z_seg = z_tbl.reshape((S, NB, theta) + z_tbl.shape[1:])
        acc_seg = acc_tbl.reshape(S, NB, theta)
    else:
        with jax.named_scope("asd/grs"):
            z_pt, acc_pt = _target_grs(
                A_pt, B_pt, y_pt, g_pt, u_pt, xi_pt, mh_pt, sig_pt,
                grs_impl, ev_ndim)

        from repro.kernels.pack import scatter_rows

        with jax.named_scope("asd/commit"):
            z_seg = scatter_rows(
                z_pt, drop_rows, S * NB * theta, impl=pack_impl
            ).reshape((S, NB, theta) + z_pt.shape[1:])
            acc_seg = (
                jnp.zeros((S * NB * theta + 1,), bool)
                .at[drop_rows].set(acc_pt)[: S * NB * theta]
                .reshape(S, NB, theta)
            )

    # --- 4. select the longest accepted prefix per slot, then commit --------
    slot_idx = jnp.arange(theta)

    def commit_one(st, plan, z_b, acc_b, gh_b, tr, br):
        n_val = jnp.minimum(tr, K - plan.a)
        acc_m = acc_b & (slot_idx[None, :] < n_val)
        lead_b = jax.vmap(leading_true_count)(acc_m)
        lead_m = jnp.where(jnp.arange(NB) < br, lead_b, -1)
        best = jnp.argmax(lead_m)  # first max: lowest branch index wins ties
        gh = None if gh_b is None else gh_b[best]
        return commit_round(
            schedule, st, plan, z_b[best], acc_m[best], tr, gh, theta,
            eager_head, keep_trajectory, controller,
            b_r=br, gain=lead_m[best] - lead_b[0], num_branches=NB,
            branch_controller=branch_controller,
        )

    if eager_head:
        return jax.vmap(commit_one)(states, plans, z_seg, acc_seg, g_head,
                                    theta_r, b_r)
    return jax.vmap(
        lambda st, plan, z, acc, tr, br: commit_one(
            st, plan, z, acc, None, tr, br)
    )(states, plans, z_seg, acc_seg, theta_r, b_r)


def packed_superstep(
    make_fn: Callable,
    params,
    schedule: Schedule,
    states,
    conds: Optional[jax.Array],
    weights: jax.Array,
    *,
    rounds: int,
    theta: int,
    budget: int,
    allocator,
    eager_head: bool = True,
    noise_mode: str = "buffer",
    keep_trajectory: bool = False,
    grs_impl: str = "core",
    controller: ThetaController = _STATIC,
    pack_impl: str = "ref",
    round_impl: str = "packed",
    fused_round: bool = False,
    budget_data=None,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
):
    """``rounds`` packed verification rounds in ONE dispatch (a ``lax.scan``).

    Each scan iteration re-runs the full plan -> allocate -> pack -> verify ->
    commit pipeline of ``packed_round`` on the DEVICE-RESIDENT slot state: the
    per-iteration budget allocation reads that iteration's ``theta_live`` /
    ``a`` (the allocator is pure jnp, so the waterfill level scan etc. trace
    straight into the scan body), and retired slots decay to masked no-ops
    exactly as in the unpacked superstep.  ``weights`` and ``conds`` are
    boundary constants: the host only re-prices slots between supersteps.

    Bit-identical to ``rounds`` sequential ``packed_round`` calls, and — at
    covering budgets — to ``asd_superstep`` per slot (tests/test_superstep.py).
    Shapes depend only on the static (rounds, budget, slots, theta) tuple.

    ``fused_round=True`` (sugar for ``round_impl="fused"``) runs every scan
    iteration through the fused kernel pair; ``budget_data`` (traced tier
    <= the static ``budget`` cap) makes the tier data instead of shape —
    see ``packed_round``.
    """
    impl = "fused" if fused_round else round_impl

    def body(ss, _):
        return packed_round(
            make_fn, params, schedule, ss, conds, weights,
            theta=theta, budget=budget, allocator=allocator,
            eager_head=eager_head, noise_mode=noise_mode,
            keep_trajectory=keep_trajectory, grs_impl=grs_impl,
            controller=controller, pack_impl=pack_impl,
            round_impl=impl, budget_data=budget_data,
            num_branches=num_branches, branch_controller=branch_controller,
        ), None

    states, _ = jax.lax.scan(body, states, None, length=int(rounds))
    return states


def sharded_packed_superstep(
    make_fn: Callable,
    params,
    schedule: Schedule,
    states,  # stacked (num_shards, S_local, ...) on every leaf
    conds: Optional[jax.Array],  # (num_shards, S_local, d_cond) or None
    weights: jax.Array,  # (num_shards, S_local)
    *,
    mesh,
    rounds: int,
    theta: int,
    budget: int,
    allocator,
    eager_head: bool = True,
    noise_mode: str = "buffer",
    keep_trajectory: bool = False,
    grs_impl: str = "core",
    controller: ThetaController = _STATIC,
    pack_impl: str = "ref",
    round_impl: str = "packed",
    fused_round: bool = False,
    budget_data=None,  # (num_shards,) i32 per-shard tiers, or None
    axis_name: str = "slots",
    param_specs=None,  # model-parallel: tp_param_pspecs tree for `params`
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
):
    """Every shard's packed superstep in ONE dispatch, via ``shard_map``
    over a ``slots``-sharded mesh (``repro.distributed.sharding.slots_mesh``
    / ``shard_pspecs``).

    The stacked slot batch (leading shard axis) is mapped over the mesh's
    ``slots`` axis: each device sees only ITS shard's (S_local, ...) block
    and runs the ordinary ``packed_superstep`` on it — the allocator splits
    the PER-SHARD ``budget`` over local demands and the pack maps address
    only local rows.  Because the body is manual-mode SPMD with no
    cross-SHARD collectives, cross-shard communication is impossible by
    construction: growing the mesh can never turn the packed gather into a
    cross-device (or cross-host) all-gather.  ``params`` are replicated
    (spec ``P()``) — unless ``param_specs`` is given.

    Model parallelism: on a 2-D ``serving_mesh(num_shards, model_parallel)``
    (axes ``(slots, model)``) pass ``param_specs`` (the ``tp_param_pspecs``
    tree) and a ``make_fn`` built with ``tp_axis="model"``.  The superstep
    then partitions over BOTH axes in this ONE dispatch: slot blocks split
    over mesh rows exactly as before (the slot batch is replicated within a
    row), verify weights split over the row's model group, and the model
    fn's all-reduces (``jax.lax.psum`` over ``"model"``) stay inside the
    program — the per-boundary dispatch count is unchanged at mp>1.

    Bit-identical to looping ``packed_superstep`` over the shard axis on one
    device (tests/test_sharded_serving.py), with ``shard_map``'s constraint
    that all shards share one static (rounds, budget, S_local, theta) tuple.
    Per-shard budget TIERS fit inside that constraint via budget-as-data:
    pass the per-shard tiers as ``budget_data`` (a (num_shards,) i32 vector,
    sharded like the slot batch) with ``budget`` as the common static cap —
    each shard's allocator splits ITS tier while every shard runs the same
    program.  Without ``budget_data``, differing tiers need the per-worker
    dispatch path (``repro.serving.sharded.ShardedASDEngine``).  On CPU,
    simulate devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    from jax.sharding import PartitionSpec as P

    impl = "fused" if fused_round else round_impl

    def one_shard(p, st, w, cond, b):
        # inside shard_map the shard axis has local size 1: peel it, run the
        # ordinary per-shard superstep, and put it back for the out_spec
        st1 = jax.tree_util.tree_map(lambda x: x[0], st)
        out = packed_superstep(
            make_fn, p, schedule, st1,
            None if cond is None else cond[0], w[0],
            rounds=rounds, theta=theta, budget=budget, allocator=allocator,
            eager_head=eager_head, noise_mode=noise_mode,
            keep_trajectory=keep_trajectory, grs_impl=grs_impl,
            controller=controller, pack_impl=pack_impl,
            round_impl=impl,
            budget_data=None if b is None else b[0],
            num_branches=num_branches, branch_controller=branch_controller,
        )
        return jax.tree_util.tree_map(lambda x: x[None], out)

    sh, rep = P(axis_name), P()
    pspec = rep if param_specs is None else param_specs
    if budget_data is None:
        if conds is None:
            fn = jax.jit(jax.shard_map(
                lambda p, st, w: one_shard(p, st, w, None, None), mesh=mesh,
                in_specs=(pspec, sh, sh), out_specs=sh, check_vma=False))
            return fn(params, states, weights)
        fn = jax.jit(jax.shard_map(
            lambda p, st, w, c: one_shard(p, st, w, c, None), mesh=mesh,
            in_specs=(pspec, sh, sh, sh), out_specs=sh, check_vma=False))
        return fn(params, states, weights, conds)
    budget_data = jnp.asarray(budget_data, jnp.int32)
    if conds is None:
        fn = jax.jit(jax.shard_map(
            lambda p, st, w, b: one_shard(p, st, w, None, b), mesh=mesh,
            in_specs=(pspec, sh, sh, sh), out_specs=sh, check_vma=False))
        return fn(params, states, weights, budget_data)
    fn = jax.jit(jax.shard_map(
        lambda p, st, w, c, b: one_shard(p, st, w, c, b), mesh=mesh,
        in_specs=(pspec, sh, sh, sh, sh), out_specs=sh, check_vma=False))
    return fn(params, states, weights, conds, budget_data)
