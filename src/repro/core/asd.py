"""Autospeculative Decoding — paper Algorithm 1, fused on-device.

One `jax.lax.while_loop` per chain; each iteration makes

  1. one model call at the current position a (the *proposal* call, line 6),
  2. a theta-step elementwise rollout of proposal means/samples using the
     pre-drawn noises xi (lines 7-9; O(theta d) FLOPs, no model calls),
  3. ONE batched model call over all theta proposal points (the *parallel
     verification round*, line 11) — on a TPU mesh this is a (theta*B)-batched
     forward sharded over the `data` axis (see DESIGN.md §2),
  4. the Verifier (Alg 2 / GRS Alg 3), a windowed commit of the accepted
     prefix + the reflected first rejection, and the advance a <- j+1.

The (u_i, xi_i) streams are drawn once, indexed by absolute step, and reused
across rounds — exactly the filtration structure the correctness proof
(Lemma 13) relies on.

Beyond-paper option ``eager_head`` ("ASD+"): the parallel round additionally
evaluates the model at the last proposal point y_hat_b.  Whenever the whole
window is accepted, that evaluation IS the next round's proposal call, so the
sequential-depth cost of a fully-accepted round drops from 2 to 1.  At the
high acceptance rates the paper reports for diffusion policies (6-7x regime)
this raises the algorithmic speedup bound from K/2R toward K/R.

Resumable-state API (the serving engine's continuous-batching substrate):

    st = init_chain_state(schedule, y0, key, theta, ...)
    while not chain_done(st, schedule.K):
        st = asd_round(model_fn, schedule, st, theta, ...)

``asd_round`` performs exactly one speculation round and is the identity on
finished chains, so a vmapped batch of ``ASDChainState`` slots can be driven
round-by-round with chains retiring (and their slots refilled) independently
— ``asd_sample`` itself is just ``init_chain_state`` + ``asd_round`` under a
``lax.while_loop`` and produces bit-identical trajectories.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.controller import (
    BranchController, StaticBranches, StaticTheta, ThetaController)
from repro.core.grs import grs, bcast_right
from repro.core.schedules import Schedule
from repro.core.sequential import init_y0
from repro.core.verifier import leading_true_count

ModelFn = Callable[[jax.Array, jax.Array], jax.Array]

# the default controller: a constant full-width window, bit-identical to the
# pre-controller sampler (see repro.core.controller for adaptive ones)
_STATIC = StaticTheta()

# the default branch controller: a constant branch count (cap = num_branches;
# num_branches == 1 is the single-draft sampler bit for bit)
_STATIC_B = StaticBranches()

# Key-fold offset separating per-branch noise streams (branches >= 1) from
# the canonical per-step folds of branch 0.  Branch b's stream is
# fold_in(fold_in(k, _BRANCH_SALT + b), step) — a pure function of (branch,
# absolute step) and the CHAIN key only, so branch draws are independent of
# slot index, shard placement, and admission order, and re-speculation stays
# deterministic (the Lemma 13 filtration argument applies per branch).
_BRANCH_SALT = 0x5D5_0000


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ASDResult:
    sample: jax.Array  # (*event) final sample y_K
    trajectory: jax.Array  # (K+1, *event) the committed chain
    rounds: jax.Array  # () int32 — iterations of the outer loop (paper's R)
    head_calls: jax.Array  # () int32 — sequential proposal calls actually made
    model_evals: jax.Array  # () int32 — total model evaluations (all slots)
    accepts: jax.Array  # () int32 — total accepted speculations
    proposals: jax.Array  # () int32 — total verified slots
    draft_points: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(0, jnp.int32)
    )  # () int32 — verified draft points across ALL branches (== proposals
    #   at num_branches == 1; the branched waste accounting reads the gap)

    def parallel_depth(self):
        """Sequential model-call depth: each round costs one parallel
        verification round plus (if not cached) one proposal call."""
        return self.rounds + self.head_calls

    def algorithmic_speedup(self, K: int):
        return K / self.parallel_depth()

    def accept_rate(self):
        return self.accepts / jnp.maximum(self.proposals, 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ASDChainState:
    """Resumable per-chain ASD state (one speculation round at a time).

    ``y`` is the committed chain: the full padded (K+theta+1, *event)
    trajectory when keep_trajectory, else the live (theta+1, *event) window
    whose slot 0 is position ``a``.  The noise streams are carried in-state
    (buffers, or just the two stream keys in counter mode) so a chain can be
    suspended, shipped across hosts, and resumed without changing its law.

    ``theta_live`` is the chain's CURRENT speculation window (<= the static
    theta_max that shapes the buffers); ``ctrl`` is the ThetaController state
    that updates it each round.  Both are plain pytree leaves, so adaptive
    windows vmap/shard exactly like the rest of the state.
    """

    y: jax.Array  # committed chain (padded trajectory or live window)
    a: jax.Array  # () int32 current position
    v_cache: jax.Array  # (*event) cached g(t_a, y_a) for eager_head
    v_valid: jax.Array  # () bool
    rounds: jax.Array
    head_calls: jax.Array
    model_evals: jax.Array
    accepts: jax.Array
    proposals: jax.Array
    theta_live: jax.Array  # () int32 current speculation window (<= theta_max)
    ctrl: jax.Array  # ThetaController state vector
    k_u: jax.Array  # uniform-stream key (counter mode)
    k_xi: jax.Array  # noise-stream key (counter mode)
    u_buf: Optional[jax.Array]  # (K+theta+1,) or None in counter mode
    xi_buf: Optional[jax.Array]  # (K+theta+1, *event) or None in counter mode
    # -- branched speculation (B exchangeable draft branches per round) ------
    b_live: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(1, jnp.int32)
    )  # () int32 current branch count (<= the static num_branches cap)
    bctrl: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.zeros((0,), jnp.float32)
    )  # BranchController state vector
    draft_points: jax.Array = dataclasses.field(
        default_factory=lambda: jnp.asarray(0, jnp.int32)
    )  # () int32 total verified draft points across ALL branches


# Backwards-compat alias: the loop state used to be private.
_State = ASDChainState


def _clamp_theta(theta: int, K: int) -> int:
    return int(min(theta, K))


def init_chain_state(
    schedule: Schedule,
    y0: jax.Array,
    key: jax.Array,
    theta: int,
    noise_mode: str = "buffer",
    keep_trajectory: bool = True,
    controller: ThetaController = _STATIC,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
) -> ASDChainState:
    """Fresh chain at position 0 with its absolute-step randomness fixed.

    The (u_i, xi_i) streams are drawn once here (lines 1-2 of Alg 1); every
    subsequent ``asd_round`` re-reads the window starting at the current
    position, which is what makes re-speculation deterministic (Lemma 13).
    ``theta`` is the static cap theta_max: it shapes the buffers, while the
    ``controller`` decides how much of the window each round actually uses.
    ``num_branches`` is the static branch cap B (branch noise streams are
    derived per round from the chain keys, so no extra buffers); the
    ``branch_controller`` decides how many branches each round actually rolls.
    """
    K = schedule.K
    theta = _clamp_theta(theta, K)
    ev_shape = y0.shape
    ctrl0, theta_live0 = controller.init(theta)
    bctrl0, b_live0 = branch_controller.init(num_branches)

    k_u, k_xi = jax.random.split(key)
    if noise_mode == "buffer":
        u_buf = jax.random.uniform(k_u, (K + theta + 1,))
        xi_buf = jax.random.normal(k_xi, (K + theta + 1,) + ev_shape, y0.dtype)
    else:
        u_buf = xi_buf = None

    if keep_trajectory:
        y_buf = jnp.zeros((K + theta + 1,) + ev_shape, y0.dtype)
    else:
        y_buf = jnp.zeros((theta + 1,) + ev_shape, y0.dtype)
    y_buf = y_buf.at[0].set(y0)

    zero = jnp.asarray(0, jnp.int32)
    return ASDChainState(
        y=y_buf,
        a=zero,
        v_cache=jnp.zeros(ev_shape, y0.dtype),
        v_valid=jnp.asarray(False),
        rounds=zero,
        head_calls=zero,
        model_evals=zero,
        accepts=zero,
        proposals=zero,
        theta_live=theta_live0,
        ctrl=ctrl0,
        k_u=k_u,
        k_xi=k_xi,
        u_buf=u_buf,
        xi_buf=xi_buf,
        b_live=b_live0,
        bctrl=bctrl0,
        draft_points=zero,
    )


def chain_done(st: ASDChainState, K: int) -> jax.Array:
    return st.a >= K


def chain_sample(st: ASDChainState, K: int, keep_trajectory: bool = True) -> jax.Array:
    """The final sample of a finished chain (either trajectory mode)."""
    if keep_trajectory:  # padded (K+theta+1) trajectory buffer
        return st.y[K]
    return st.y[0]  # live window: slot 0 is position a == K on exit


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RoundPlan:
    """Everything one speculation round computes BEFORE the parallel
    verification model call: the proposal call's output, the theta-step
    elementwise rollout, and the schedule/noise windows it consumed.

    ``plan_round`` produces it; the dense path (``asd_round``) verifies the
    whole theta_max-shaped window against it, while the packed path
    (``repro.serving.packing``) gathers only each slot's LIVE points across
    a slot batch of plans into one budget-shaped model call.  All leaves are
    per-chain arrays, so a ``RoundPlan`` vmaps exactly like ``ASDChainState``.
    """

    a: jax.Array  # () i32 chain position entering the round
    theta_live: jax.Array  # () i32 clipped live window
    n_valid: jax.Array  # () i32 live verification points: min(theta_live, K-a)
    v_a: jax.Array  # (*event) proposal-call output g(t_a, y_a)
    new_head: jax.Array  # () i32 — 1 if the proposal call was actually made
    y_prev: jax.Array  # (theta, *event) verification inputs y_{a+j}
    y_props: jax.Array  # (theta, *event) proposal samples y_hat_{a+j+1}
    m_hats: jax.Array  # (theta, *event) proposal means
    t_w1: jax.Array  # (theta+1,) model times t_a .. t_{a+theta}
    u_w: jax.Array  # (theta,) verifier uniforms
    xi_w: jax.Array  # (theta, *event) step noises
    A_w: jax.Array  # (theta,)
    B_w: jax.Array  # (theta,)
    sig_w: jax.Array  # (theta,)
    # -- branched speculation: (B, theta, ...) stacks over ALL draft branches.
    # Row 0 is bit-identical to the canonical leaves above; rows >= 1 come
    # from per-branch key folds.  None when the plan was built single-draft.
    y_prev_b: Optional[jax.Array] = None  # (B, theta, *event)
    y_props_b: Optional[jax.Array] = None  # (B, theta, *event)
    m_hats_b: Optional[jax.Array] = None  # (B, theta, *event)
    u_w_b: Optional[jax.Array] = None  # (B, theta)
    xi_w_b: Optional[jax.Array] = None  # (B, theta, *event)


def _window(arr, start, length):
    return jax.lax.dynamic_slice_in_dim(arr, start, length, axis=0)


@jax.named_scope("asd/propose")
def plan_round(
    model_fn: ModelFn,
    schedule: Schedule,
    st: ASDChainState,
    theta: int,
    eager_head: bool = False,
    noise_mode: str = "buffer",
    keep_trajectory: bool = True,
    num_branches: int = 1,
) -> RoundPlan:
    """Phase 1 of a speculation round (Alg 1 lines 6-9): the sequential
    proposal call (possibly served from the eager cache) plus the theta-step
    elementwise proposal rollout.  No parallel model call happens here.

    With ``num_branches`` B > 1 the rollout runs B independent draft
    branches from the same proposal output v_a: branch 0 consumes the
    canonical noise stream (bit-identical to the single-draft plan), branches
    b >= 1 draw (u, xi) from per-branch folds of the chain keys.  The
    branched stacks land in the ``*_b`` plan fields; the canonical 2-D
    leaves always hold branch 0, so every single-draft consumer is
    unchanged."""
    K = schedule.K
    theta = _clamp_theta(theta, K)
    sched = schedule.pad(theta + 1)
    ev_shape = st.v_cache.shape
    dtype = st.y.dtype
    theta_live = jnp.clip(st.theta_live, 1, theta)

    def noise_window(a):
        if noise_mode == "buffer":
            return _window(st.u_buf, a, theta), _window(st.xi_buf, a, theta)
        idx = a + jnp.arange(theta)
        u_w = jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(st.k_u, i), ()))(idx)
        xi_w = jax.vmap(
            lambda i: jax.random.normal(jax.random.fold_in(st.k_xi, i), ev_shape, dtype)
        )(idx)
        return u_w, xi_w

    a = st.a
    if keep_trajectory:
        y_a = jax.lax.dynamic_index_in_dim(st.y, a, axis=0, keepdims=False)
    else:
        y_a = st.y[0]
    t_a = sched.t_model[a]

    # --- 1. proposal call (line 6), possibly served from the eager cache
    if eager_head:
        v_a = jnp.where(st.v_valid, st.v_cache, _call1(model_fn, t_a, y_a))
        new_head = jnp.where(st.v_valid, 0, 1)
    else:
        v_a = _call1(model_fn, t_a, y_a)
        new_head = jnp.asarray(1, jnp.int32)

    # --- 2. theta-step proposal rollout (lines 7-9)
    A_w = _window(sched.A, a, theta)
    B_w = _window(sched.B, a, theta)
    sig_w = _window(sched.sigma, a, theta)
    t_w1 = _window(sched.t_model, a, theta + 1)
    u_w, xi_w = noise_window(a)

    def roll(y_i, inp):
        A, B, sg, x = inp
        m_hat = A * y_i + B * v_a
        y_next = m_hat + sg * x
        return y_next, (m_hat, y_next)

    _, (m_hats, y_props) = jax.lax.scan(roll, y_a, (A_w, B_w, sig_w, xi_w))
    y_prev = jnp.concatenate([y_a[None], y_props[:-1]], axis=0)  # (theta, ev)

    branched = {}
    if num_branches > 1:
        # branches >= 1: per-branch counter-style streams (both noise modes)
        idx = a + jnp.arange(theta)

        def branch_noise(b):
            kb_u = jax.random.fold_in(st.k_u, _BRANCH_SALT + b)
            kb_xi = jax.random.fold_in(st.k_xi, _BRANCH_SALT + b)
            u_b = jax.vmap(
                lambda i: jax.random.uniform(jax.random.fold_in(kb_u, i), ())
            )(idx)
            xi_b = jax.vmap(
                lambda i: jax.random.normal(
                    jax.random.fold_in(kb_xi, i), ev_shape, dtype)
            )(idx)
            return u_b, xi_b

        u_r, xi_r = jax.vmap(branch_noise)(jnp.arange(1, num_branches))

        def roll_branch(xi_b):
            _, (mh, yp) = jax.lax.scan(roll, y_a, (A_w, B_w, sig_w, xi_b))
            return mh, yp

        mh_r, yp_r = jax.vmap(roll_branch)(xi_r)  # (B-1, theta, *event)
        y_props_b = jnp.concatenate([y_props[None], yp_r], axis=0)
        y_prev_b = jnp.concatenate(
            [jnp.broadcast_to(
                y_a, (num_branches, 1) + ev_shape), y_props_b[:, :-1]],
            axis=1)
        branched = dict(
            y_prev_b=y_prev_b,
            y_props_b=y_props_b,
            m_hats_b=jnp.concatenate([m_hats[None], mh_r], axis=0),
            u_w_b=jnp.concatenate([u_w[None], u_r], axis=0),
            xi_w_b=jnp.concatenate([xi_w[None], xi_r], axis=0),
        )

    return RoundPlan(
        a=a,
        theta_live=theta_live,
        n_valid=jnp.minimum(theta_live, K - a),
        v_a=v_a,
        new_head=new_head,
        y_prev=y_prev,
        y_props=y_props,
        m_hats=m_hats,
        t_w1=t_w1,
        u_w=u_w,
        xi_w=xi_w,
        A_w=A_w,
        B_w=B_w,
        sig_w=sig_w,
        **branched,
    )


@jax.named_scope("asd/commit")
def commit_round(
    schedule: Schedule,
    st: ASDChainState,
    plan: RoundPlan,
    z: jax.Array,
    acc: jax.Array,
    theta_r: jax.Array,
    g_head: Optional[jax.Array],
    theta: int,
    eager_head: bool = False,
    keep_trajectory: bool = True,
    controller: ThetaController = _STATIC,
    *,
    b_r: Optional[jax.Array] = None,
    gain: Optional[jax.Array] = None,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
) -> ASDChainState:
    """Phase 3 of a speculation round (Alg 1 lines 12-13): windowed commit of
    the accepted prefix + the reflected first rejection, counter updates, and
    the controller's window update.

    ``z``/``acc`` are the theta_max-shaped verifier outputs — only slots
    ``< min(theta_r, K - a)`` are read.  ``theta_r`` is the window THIS round
    effectively ran: ``plan.theta_live`` on the dense path, the slot's budget
    grant on the packed path (a pre-round-measurable quantity either way, so
    the committed chain's law is unchanged).  Identity on finished chains.

    Branched rounds pass the SELECTED branch's ``z``/``acc``/``g_head`` plus
    ``b_r`` (branches the round effectively ran — the cost multiplier for
    model_evals/draft_points) and ``gain`` (the winning branch's extra
    accepted slots over branch 0 — the BranchController observable).
    """
    K = schedule.K
    theta = _clamp_theta(theta, K)
    ev_shape = st.v_cache.shape
    ev_ndim = st.v_cache.ndim
    dtype = st.y.dtype
    a = plan.a

    n_valid = jnp.minimum(theta_r, K - a)
    slot = jnp.arange(theta)
    acc = acc & (slot < n_valid)
    lead = leading_true_count(acc)
    rejected = lead < n_valid
    advance = lead + jnp.where(rejected, 1, 0)

    if keep_trajectory:
        old = _window(st.y, a + 1, theta)
    else:
        old = st.y[1:]
    mask = bcast_right(slot < advance, ev_ndim + 1)
    committed = jnp.where(mask, z, old)
    if keep_trajectory:
        y_new = jax.lax.dynamic_update_slice_in_dim(
            st.y, committed, a + 1, axis=0
        )
    else:
        # shift the live window so slot 0 becomes position a + advance
        buf2 = jnp.concatenate(
            [st.y[:1], committed,
             jnp.zeros((theta,) + ev_shape, dtype)], axis=0
        )
        y_new = jax.lax.dynamic_slice_in_dim(buf2, advance, theta + 1, axis=0)

    # n_valid > 0 guards the packed path's zero-grant stall: a round that
    # verified nothing must not validate the eager-head cache
    full_accept = (~rejected) & (n_valid == theta_r) & (n_valid > 0)
    ctrl_new, theta_next = controller.update(
        st.ctrl, theta_r, lead, n_valid, rejected, theta
    )
    # b_eff = 1 on every single-draft path reproduces the original counter
    # arithmetic bit for bit; branched rounds scale verification cost by the
    # branch count they effectively ran
    b_eff = jnp.asarray(1, jnp.int32) if b_r is None else b_r
    if num_branches > 1:
        bctrl_new, b_next = branch_controller.update(
            st.bctrl, b_eff,
            jnp.asarray(0, jnp.int32) if gain is None else gain,
            lead, rejected, num_branches,
        )
        b_next = jnp.clip(b_next, 1, num_branches)
    else:
        bctrl_new, b_next = st.bctrl, st.b_live
    new = ASDChainState(
        y=y_new,
        a=a + advance,
        v_cache=g_head if eager_head else st.v_cache,
        v_valid=full_accept if eager_head else jnp.asarray(False),
        rounds=st.rounds + 1,
        head_calls=st.head_calls + plan.new_head,
        model_evals=st.model_evals
        + plan.new_head
        + b_eff * n_valid
        + (b_eff if eager_head else 0),
        accepts=st.accepts + lead,
        proposals=st.proposals + n_valid,
        theta_live=jnp.clip(theta_next, 1, theta),
        ctrl=ctrl_new,
        k_u=st.k_u,
        k_xi=st.k_xi,
        u_buf=st.u_buf,
        xi_buf=st.xi_buf,
        b_live=b_next,
        bctrl=bctrl_new,
        draft_points=st.draft_points + b_eff * n_valid,
    )
    return _where_tree(a < K, new, st)


def asd_round(
    model_fn: ModelFn,
    schedule: Schedule,
    st: ASDChainState,
    theta: int,
    eager_head: bool = False,
    noise_mode: str = "buffer",
    keep_trajectory: bool = True,
    grs_impl: str = "core",
    controller: ThetaController = _STATIC,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
) -> ASDChainState:
    """One speculation round (Alg 1 lines 5-13): propose, roll theta steps,
    verify in ONE batched model call, commit the accepted prefix.

    ``num_branches`` B > 1 rolls B exchangeable draft branches from the same
    proposal output, scores all B x theta points in the one batched call, and
    commits the branch with the LONGEST accepted prefix (deterministic
    lowest-index tie-break).  Each branch's committed window is an exact
    draw of the next steps of the target chain (Thm 12 applies per branch,
    and the branch count is F_a-measurable), and branch increments are
    exchangeable — so selection only changes WHICH exact continuation gets
    committed.  ``num_branches == 1`` compiles the original single-draft
    body: bit-identical to today, by construction.

    ``theta`` is the static cap theta_max.  The round always rolls and
    dispatches ``theta``-shaped buffers — so the compiled program is shared
    across every value of the per-chain live window — but only
    ``st.theta_live`` slots are verified (the ``n_valid`` mask) and counted,
    and the ``controller`` updates ``theta_live`` from the round's observed
    accepts before the state is returned.

    Internally this is ``plan_round`` (proposal + rollout) -> one dense
    theta_max-shaped verification call -> ``commit_round``; the packed
    execution path (``repro.serving.packing``) reuses the same plan/commit
    phases but gathers only the live points across a slot batch.

    Identity on finished chains (a >= K): under vmap a slot whose chain has
    retired keeps its state (and counters) frozen while its neighbours keep
    speculating — the property continuous batching relies on.  The static
    arguments (theta, eager_head, noise_mode, keep_trajectory, controller)
    must match the ``init_chain_state`` call that produced ``st``.
    """
    K = schedule.K
    theta = _clamp_theta(theta, K)
    ev_ndim = st.v_cache.ndim

    plan = plan_round(
        model_fn, schedule, st, theta, eager_head, noise_mode,
        keep_trajectory, num_branches,
    )
    theta_live = plan.theta_live

    if num_branches > 1:
        z, acc, g_head, b_r, gain = _branched_verify_select(
            model_fn, st, plan, theta, num_branches, eager_head, grs_impl)
        return commit_round(
            schedule, st, plan, z, acc, theta_live, g_head, theta,
            eager_head, keep_trajectory, controller,
            b_r=b_r, gain=gain, num_branches=num_branches,
            branch_controller=branch_controller,
        )

    t_w = plan.t_w1[:theta]
    y_prev = plan.y_prev

    # --- 3. ONE batched parallel round (line 11)
    with jax.named_scope("asd/verify"):
        if eager_head:
            # the head slot sits at the END of the LIVE window: on a full
            # accept the chain lands on y_props[theta_live - 1], so this
            # evaluation IS the next round's proposal call
            y_head = jax.lax.dynamic_index_in_dim(
                plan.y_props, theta_live - 1, axis=0, keepdims=True
            )
            pts = jnp.concatenate([y_prev, y_head], axis=0)
            ts = jnp.concatenate([t_w, plan.t_w1[theta_live][None]], axis=0)
            g_all = model_fn(ts, pts)
            g_par, g_head = g_all[:-1], g_all[-1]
        else:
            g_par = model_fn(t_w, y_prev)
            g_head = None

    # --- 4. Verifier (Alg 2) + windowed commit
    with jax.named_scope("asd/grs"):
        m_tgt = bcast_right(plan.A_w, ev_ndim + 1) * y_prev + bcast_right(
            plan.B_w, ev_ndim + 1
        ) * g_par
        if grs_impl == "kernel":
            from repro.kernels.grs.ops import grs as grs_k

            z, acc = grs_k(plan.u_w, plan.xi_w, plan.m_hats, m_tgt,
                           plan.sig_w, event_ndim=ev_ndim)
        else:
            z, acc = grs(plan.u_w, plan.xi_w, plan.m_hats, m_tgt, plan.sig_w,
                         event_ndim=ev_ndim)
    return commit_round(
        schedule, st, plan, z, acc, theta_live, g_head, theta,
        eager_head, keep_trajectory, controller,
    )


def _branched_verify_select(
    model_fn: ModelFn,
    st: ASDChainState,
    plan: RoundPlan,
    theta: int,
    num_branches: int,
    eager_head: bool,
    grs_impl: str,
):
    """Phase 2 of a BRANCHED round: one (B*theta)-point verification call,
    per-branch GRS, and longest-accepted-prefix selection.

    Like the dense single-draft round, shapes are static at the cap — all B
    branches' points ride in the one batched call and only branches
    ``< st.b_live`` compete (dead lanes are masked out of the argmax), so the
    compiled program is shared across every live branch count.

    Returns ``(z, acc, g_head, b_r, gain)`` for ``commit_round``: the
    selected branch's verifier outputs, its eager-head evaluation, the
    effective branch count, and the winning branch's accepted-slot gain over
    branch 0 (the BranchController observable).
    """
    B = num_branches
    ev_shape = st.v_cache.shape
    ev_ndim = st.v_cache.ndim
    theta_live = plan.theta_live
    b_live = jnp.clip(st.b_live, 1, B)
    t_w = plan.t_w1[:theta]

    y_prev_f = plan.y_prev_b.reshape((B * theta,) + ev_shape)
    ts_f = jnp.tile(t_w, B)
    with jax.named_scope("asd/verify"):
        if eager_head:
            # one head point PER BRANCH at the end of the live window:
            # whichever branch wins a full accept, its head evaluation is the
            # next round's proposal call
            heads = jax.vmap(
                lambda yp: jax.lax.dynamic_index_in_dim(
                    yp, theta_live - 1, axis=0, keepdims=False)
            )(plan.y_props_b)  # (B, *event)
            pts = jnp.concatenate([y_prev_f, heads], axis=0)
            ts = jnp.concatenate(
                [ts_f, jnp.broadcast_to(plan.t_w1[theta_live], (B,))],
                axis=0)
            g_all = model_fn(ts, pts)
            g_par = g_all[: B * theta].reshape((B, theta) + ev_shape)
            g_heads = g_all[B * theta:]
        else:
            g_par = model_fn(ts_f, y_prev_f).reshape((B, theta) + ev_shape)
            g_heads = None

    with jax.named_scope("asd/grs"):
        m_tgt = (
            bcast_right(plan.A_w, ev_ndim + 1) * plan.y_prev_b
            + bcast_right(plan.B_w, ev_ndim + 1) * g_par
        )
        sig_bt = jnp.broadcast_to(plan.sig_w, (B, theta))
        if grs_impl == "kernel":
            from repro.kernels.grs.ops import grs as grs_k

            z_b, acc_b = grs_k(plan.u_w_b, plan.xi_w_b, plan.m_hats_b, m_tgt,
                               sig_bt, event_ndim=ev_ndim)
        else:
            z_b, acc_b = grs(plan.u_w_b, plan.xi_w_b, plan.m_hats_b, m_tgt,
                             sig_bt, event_ndim=ev_ndim)

    slot = jnp.arange(theta)
    acc_m = acc_b & (slot[None, :] < plan.n_valid)  # (B, theta)
    lead_b = jax.vmap(leading_true_count)(acc_m)  # (B,)
    live = jnp.arange(B) < b_live
    lead_m = jnp.where(live, lead_b, -1)
    best = jnp.argmax(lead_m)  # argmax takes the FIRST max: lowest index wins
    z = z_b[best]
    acc = acc_m[best]
    g_head = g_heads[best] if eager_head else None
    gain = lead_m[best] - lead_b[0]
    return z, acc, g_head, b_live, gain


def _where_tree(pred, new, old):
    """Leaf-wise select; keeps finished chains frozen under vmap."""
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(bcast_right(pred, n.ndim), n, o), new, old
    )


def asd_superstep(
    model_fn: ModelFn,
    schedule: Schedule,
    st: ASDChainState,
    theta: int,
    rounds: int,
    eager_head: bool = False,
    noise_mode: str = "buffer",
    keep_trajectory: bool = True,
    grs_impl: str = "core",
    controller: ThetaController = _STATIC,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
) -> ASDChainState:
    """``rounds`` speculation rounds in ONE device dispatch (a ``lax.scan``).

    The scan body is exactly ``asd_round``, so a chain that commits its final
    step mid-superstep becomes a masked no-op for the remaining iterations:
    every leaf of its state — committed chain, counters, controller state —
    is preserved bit for bit by the ``a < K`` select inside ``commit_round``.
    ``asd_superstep(R)`` is therefore bit-identical to R sequential
    ``asd_round`` calls (asserted in tests/test_superstep.py), while paying
    ONE dispatch and ONE host sync where the sequential drive pays R.

    This is the device-resident substrate of the serving engine's
    ``rounds_per_sync``: the host only intervenes (retire, admit, reweight)
    at superstep boundaries.  ``rounds`` is static — each value compiles its
    own program, so callers should draw it from a small ladder (the engine
    uses powers of two).
    """
    def body(s, _):
        return asd_round(
            model_fn, schedule, s, theta, eager_head, noise_mode,
            keep_trajectory, grs_impl, controller, num_branches,
            branch_controller,
        ), None

    st, _ = jax.lax.scan(body, st, None, length=int(rounds))
    return st


def asd_sample(
    model_fn: ModelFn,
    schedule: Schedule,
    y0: jax.Array,
    key: jax.Array,
    theta: int,
    eager_head: bool = False,
    noise_mode: str = "buffer",
    keep_trajectory: bool = True,
    grs_impl: str = "core",
    controller: ThetaController = _STATIC,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
) -> ASDResult:
    """Run ASD for one chain.  ``theta >= K`` gives ASD-infinity.

    model_fn(t: f32[m], y: f32[m, *event]) -> f32[m, *event] must accept any
    leading batch size m (it is called with m=1 and m=theta(+1)).

    ``theta`` is the window CAP; the ``controller`` (default: the static
    full-width window, bit-identical to the original sampler) adapts the live
    window per round from observed accepts — see repro.core.controller.

    Beyond-paper memory options (identical law; see EXPERIMENTS.md §Perf):
      * noise_mode="counter": derive (u_i, xi_i) from a counter-based PRNG
        fold at absolute step i instead of materializing O(K*d) buffers —
        the re-speculation determinism the proof needs is preserved because
        fold_in(key, i) is a pure function of i.
      * keep_trajectory=False: keep only the (theta+1)-slot live window of
        the chain instead of the full (K+1)-step trajectory; the
        ``trajectory`` field then holds the final window.
    """
    K = schedule.K
    theta = _clamp_theta(theta, K)

    st0 = init_chain_state(
        schedule, y0, key, theta, noise_mode, keep_trajectory, controller,
        num_branches, branch_controller,
    )

    def cond(st: ASDChainState):
        return st.a < K

    def body(st: ASDChainState):
        return asd_round(
            model_fn, schedule, st, theta, eager_head, noise_mode,
            keep_trajectory, grs_impl, controller, num_branches,
            branch_controller,
        )

    st = jax.lax.while_loop(cond, body, st0)
    if keep_trajectory:
        traj = st.y[: K + 1]
    else:
        traj = st.y  # the final (theta+1) live window
    return ASDResult(
        sample=chain_sample(st, K, keep_trajectory),
        trajectory=traj,
        rounds=st.rounds,
        head_calls=st.head_calls,
        model_evals=st.model_evals,
        accepts=st.accepts,
        proposals=st.proposals,
        draft_points=st.draft_points,
    )


def _call1(model_fn: ModelFn, t, y):
    return model_fn(t[None], y[None])[0]


def asd_sample_batched(
    model_fn: ModelFn,
    schedule: Schedule,
    y0: jax.Array,  # (B, *event)
    key: jax.Array,
    theta: int,
    eager_head: bool = False,
    noise_mode: str = "buffer",
    keep_trajectory: bool = True,
    controller: ThetaController = _STATIC,
    num_branches: int = 1,
    branch_controller: BranchController = _STATIC_B,
) -> ASDResult:
    """Independent ASD chains vmapped over a batch.

    Under vmap the per-round batched model call becomes a (B*theta)-point
    forward — the SPMD form that shards over the mesh `data` axis.  Chains
    finish at different rounds; the fused loop runs to the slowest chain
    (standard batched speculative serving semantics).  The continuous-
    batching engine in ``repro.serving.engine`` avoids that straggler waste
    by driving ``asd_round`` itself and refilling retired slots.
    """
    keys = jax.random.split(key, y0.shape[0])
    fn = lambda y, k: asd_sample(
        model_fn, schedule, y, k, theta, eager_head, noise_mode,
        keep_trajectory, controller=controller, num_branches=num_branches,
        branch_controller=branch_controller,
    )
    return jax.vmap(fn)(y0, keys)


def asd_init_y0(schedule: Schedule, key, event_shape, dtype=jnp.float32):
    return init_y0(schedule, key, event_shape, dtype)
