"""The comparison that decides ``correct``.

Each configuration names the numbers it compares, with their limits,
under ``limits``; the others are reported and not judged.

After the window closes the harness holds, for fresh chains, the state
each was in at a superstep boundary ("pre") and the state the timed
program left it in one superstep later ("post").  The reference replays the
same rounds from "pre" (``reference.replay``) and these numbers compare
the two:

``impossible``
    Chains whose steps, accepts and rounds over the superstep no sequence
    of rounds can produce: no step committed, more accepts than steps, more
    steps than theta a round.  Exact: its limit is 0.
``flip_share``
    The share of chains whose committed steps, accepts or rounds differ
    from the reference's: GRS decisions taken the other way.  A sound
    program flips only decisions that lie within rounding of the accept
    threshold.  A flip of the last step's decision, where sigma is 0 and
    the two means' equality decides and both branches commit the target
    mean, is not counted: rounding decides it either way.
``decision_gap``
    For a chain that flipped, the margin of the accept decision that must
    have flipped: how far the reference's draw lay from its threshold.
    With one round per superstep the flipped decision is known exactly;
    with more, the smallest margin among the reference's decisions of the
    superstep is taken, which can only understate it.  An outcome that no
    round can produce reads infinity.  0 where every chain agrees.
``y_rel_err``
    For chains that agree, ||y_post - y_ref|| / ||y_ref|| of the committed
    point, the largest.
``y_full_rel_err``
    The same, over the chains whose every round accepted its whole window:
    the rollout with the proposal call's mean and its commit, without the
    reflection.
``reflect_err``
    Over the chains that agree and whose last round ended in a rejection,
    the median of ||y_post - z_ref|| / ||z_ref - w_ref||: how far the
    committed point lies from the reference's reflected one, as a share of
    how far the reflection moved it (w_ref is the point without it).  A
    commit that skips or botches the reflection reads about 1.
``x0_rel_err``
    For chains whose round accepted its whole window, the program caches
    its verification call's x0 prediction at the new point; the reference
    evaluates the same point.  Relative L2 error, the largest: the
    denoiser call at full width, alone.  Absent when no checked chain
    accepted a whole window.
"""

from __future__ import annotations

import math

import numpy as np

from chipbench.reference import replay


def _rel(x, ref) -> float:
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(np.asarray(x, np.float64) - ref)
                 / max(np.linalg.norm(ref), 1e-30))


def compare(pre: list, post: list, sched: dict, theta: int, rounds: int,
            model) -> dict:
    """Numbers over the chains of ``pre``/``post`` (matching lists of dicts
    with a, y, k_u, k_xi, accepts, rounds, v_valid, v_cache)."""
    gap, y_err, y_full, x0_err = 0.0, 0.0, None, 0.0
    n_x0, n_agree, bad, flips, reflect = 0, 0, 0, 0, []
    for p, q in zip(pre, post):
        ref = replay(p, sched, theta, rounds, model)
        da = int(q["a"]) - int(p["a"])
        dacc = int(q["accepts"]) - int(p["accepts"])
        drounds = int(q["rounds"]) - int(p["rounds"])
        got = (da, dacc, drounds)
        want = (ref["a"] - int(p["a"]), ref["accepts"], ref["rounds"])
        if got != want:
            m = _flipped_margin(got, want, ref["margins"], theta, rounds)
            bad += math.isinf(m)
            flips += m > 0
            gap = max(gap, m)
            continue
        n_agree += 1
        e = _rel(q["y"], ref["y"])
        y_err = max(y_err, e)
        if ref["rejections"] == 0:
            y_full = max(y_full or 0.0, e)
        if ref["unreflected"] is not None:
            moved = np.linalg.norm(ref["y"] - ref["unreflected"])
            if moved > 0:
                reflect.append(float(np.linalg.norm(
                    np.asarray(q["y"], np.float64) - ref["y"]) / moved))
        if ref["head"] is not None and bool(q["v_valid"]):
            t = ref["head"][0]
            x0 = model(np.array([t]), np.asarray(q["y"], np.float64)[None])[0]
            x0_err = max(x0_err, _rel(q["v_cache"], x0))
            n_x0 += 1
    out = {"impossible": bad, "flip_share": flips / max(len(pre), 1),
           "decision_gap": gap, "y_rel_err": y_err, "chains": len(pre),
           "agree": n_agree, "x0_points": n_x0,
           "reflect_points": len(reflect)}
    if y_full is not None:
        out["y_full_rel_err"] = y_full
    if reflect:
        out["reflect_err"] = float(np.median(reflect))
    if n_x0:
        out["x0_rel_err"] = x0_err
    return out


def _flipped_margin(got, want, margins, theta, rounds) -> float:
    da, dacc, drounds = got
    if drounds < 1 or da < drounds or dacc > da or da > theta * drounds:
        return math.inf  # no sequence of rounds commits this
    if rounds == 1 and want[2] == 1:
        # one round: the program's lead is dacc, the reference's want[1];
        # the first decision on which they part is the one that flipped
        j = min(dacc, want[1])
        if j < len(margins):
            return abs(margins[j])
        return math.inf
    return min((abs(m) for m in margins), default=math.inf)


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number the limits name was
    read and is at or below its limit, and at least one chain was compared.
    A number that could not be read (no x0 point) is printed as None."""
    rows = [(name, numbers.get(name), limit) for name, limit in limits.items()]
    ok = numbers.get("chains", 0) > 0 and all(
        v is not None and v <= limit for _, v, limit in rows)
    return ok, rows


def control_posts(pre: list, sched: dict, theta: int, rounds: int,
                  model) -> list:
    """The states the reference itself leaves after ``rounds`` rounds from
    ``pre`` with ``model``: the reference put in the program's place, in
    the shape of the program's post states."""
    out = []
    for p in pre:
        r = replay(p, sched, theta, rounds, model)
        post = {"a": r["a"], "y": r["y"],
                "accepts": int(p["accepts"]) + r["accepts"],
                "rounds": int(p["rounds"]) + r["rounds"],
                "v_valid": r["head"] is not None, "v_cache": None}
        if r["head"] is not None:
            t, y = r["head"]
            post["v_cache"] = model(np.array([t]), y[None])[0]
        out.append(post)
    return out
