"""The plain reference: the DDPM schedule and one autospeculative round,
written from their published descriptions, around the denoiser of the
configuration's family (``chipbench/families/<reference>.py``).

Nothing here imports the program.  The round follows the paper's
Algorithm 1 with the Gaussian rejection sampler of its Algorithm 3
(arXiv:2505.03983): a proposal call at the chain's position, a theta-step
rollout with the stale mean, one verification call over the theta points,
the longest accepted prefix, and the reflected first rejection.  A
family's denoiser computes every matrix product through ``mm``, at
``precision=HIGHEST``.

``lower`` computes the denoiser's matrix products with operands rounded to
a lower precision (``bfloat16``, or ``float8_e4m3fn`` with one scale per
tensor): the control that the comparison must reject.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ the schedule


def ddpm_schedule(K: int, kind: str = "cosine") -> dict:
    """Ancestral DDPM over an x0-predicting model, in denoising order:
    step i (0-based) moves from diffusion time s = K - i to s - 1 with
    mean A y + B x0_hat and standard deviation sigma (Ho et al. 2020, eq. 7;
    the cosine betas of Nichol & Dhariwal 2021, eq. 17)."""
    if kind == "cosine":
        steps = np.arange(K + 1, dtype=np.float64) / K
        f = np.cos((steps + 0.008) / 1.008 * np.pi / 2) ** 2
        betas = np.clip(1.0 - f[1:] / f[:-1], 0.0, 0.999)
    elif kind == "linear":
        betas = np.linspace(1e-4 * 1000 / K, 0.02 * 1000 / K, K)
    else:
        raise ValueError(kind)
    alphas = 1.0 - betas
    abar = np.cumprod(alphas)
    abar_prev = np.concatenate([[1.0], abar[:-1]])
    A = np.sqrt(alphas) * (1.0 - abar_prev) / (1.0 - abar)
    B = np.sqrt(abar_prev) * betas / (1.0 - abar)
    var = betas * (1.0 - abar_prev) / (1.0 - abar)
    rev = slice(None, None, -1)
    return {"t": np.arange(K, dtype=np.float64)[rev], "A": A[rev],
            "B": B[rev], "sigma": np.sqrt(var[rev]), "K": K}


# ------------------------------------------------------------ matrix products


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and back to float32; float8 takes one
    scale per tensor, so that its range fits the tensor's."""
    x = x.astype(jnp.float32)
    if dtype == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if dtype == "float8_e4m3fn":
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(dtype)


def mm(spec, a, b, lower=None):
    """``einsum(spec, a, b)`` in float32 at ``HIGHEST``, its operands first
    rounded to ``lower`` where it is given."""
    if lower:
        a, b = _round(a, lower), _round(b, lower)
    return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=HIGHEST)


def denoise_blocks(denoise, w, t, y, m, lower=None, block=8):
    """A family's ``denoise`` over a batch of points, ``block`` points at a
    time (a lone point on its own), so that the reference fits beside
    nothing else on the device and compiles at most two shapes."""
    items = tuple(sorted(m.items()))
    out = []
    for i in range(0, len(t), block):
        tb, yb = np.asarray(t[i:i + block]), np.asarray(y[i:i + block])
        n = len(tb)
        width = 1 if n == 1 else block
        if width > n:  # one compiled shape for every block
            tb = np.concatenate([tb, np.repeat(tb[-1:], width - n)])
            yb = np.concatenate([yb, np.repeat(yb[-1:], width - n, axis=0)])
        r = denoise(w, jnp.asarray(tb, jnp.float32),
                    jnp.asarray(yb, jnp.float32), items, lower)
        out.append(np.asarray(r, np.float64)[:n])
    return np.concatenate(out)


# -------------------------------------------------------------- the round


@partial(jax.jit, static_argnames=("event",))
def _noise(k_u, k_xi, steps, event):
    u = jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(k_u, i), ()))(
        steps)
    xi = jax.vmap(lambda i: jax.random.normal(
        jax.random.fold_in(k_xi, i), event, jnp.float32))(steps)
    return u, xi


def step_noise(k_u, k_xi, steps, event):
    """(u, xi) of the chain's absolute steps: one uniform and one standard
    normal event per step, each from the chain key folded with the step."""
    u, xi = _noise(jnp.asarray(k_u), jnp.asarray(k_xi), steps, tuple(event))
    return np.asarray(u, np.float64), np.asarray(xi, np.float64)


def replay(chain: dict, sched: dict, theta: int, rounds: int, model):
    """Replay ``rounds`` speculation rounds of one chain from its state.

    ``chain``: position ``a``, the committed point ``y`` at a, and the
    noise keys ``k_u``/``k_xi``.  ``model(t, y) -> x0_hat`` evaluates a
    batch of points.  Returns the state after the rounds (a, y, the counts
    of rounds, accepts, steps and rejections) and every accept decision's
    margin log(u) - min(log_ratio, 0): accept iff it is <= 0, and a decision
    whose margin lies near 0 may flip on rounding.  A decision at sigma == 0
    is decided by equality and its margin is 0.  Where the last round ends
    in a rejection, ``unreflected`` is the point it would commit without
    the reflection, m + sigma * xi.
    """
    K = sched["K"]
    pad = lambda x, c: np.concatenate([x, np.full(theta + 1, c)])  # noqa: E731
    A, B = pad(sched["A"], 1.0), pad(sched["B"], 0.0)
    sig, tt = pad(sched["sigma"], 0.0), pad(sched["t"], sched["t"][-1])
    a, y = int(chain["a"]), np.asarray(chain["y"], np.float64)
    event = y.shape
    margins, accepts, n_rounds, full = [], 0, 0, False
    head = unreflected = None
    rejections = 0
    for _ in range(rounds):
        if a >= K:
            break
        n = min(theta, K - a)
        u, xi = step_noise(chain["k_u"], chain["k_xi"],
                           jnp.arange(a, a + theta), event)
        v_a = model(np.array([tt[a]]), y[None])[0]
        ys, m_hat = [y], []
        for j in range(theta):
            m_hat.append(A[a + j] * ys[j] + B[a + j] * v_a)
            ys.append(m_hat[j] + sig[a + j] * xi[j])
        g = model(tt[a:a + n], np.stack(ys[:n]))
        lead, z, unreflected = 0, None, None
        for j in range(n):
            m_t = A[a + j] * ys[j] + B[a + j] * g[j]
            v = m_hat[j] - m_t
            vv, vx = float((v * v).sum()), float((v * xi[j]).sum())
            s = sig[a + j]
            if s > 0:
                log_ratio = -(vx / s + vv / (2 * s * s))
                margin = math.log(max(u[j], 1e-20)) - min(log_ratio, 0.0)
                ok = margin <= 0
            else:
                margin, ok = 0.0, vv <= 0.0
            margins.append(margin)
            if not ok:
                xi_ref = xi[j] - (2 * vx / vv) * v if vv > 0 else xi[j]
                z = m_t + s * xi_ref
                unreflected = m_t + s * xi[j]
                break
            lead += 1
        rejected = lead < n
        rejections += rejected
        adv = lead + (1 if rejected else 0)
        y = z if rejected else ys[adv]
        full = not rejected and n == theta
        a += adv
        accepts += lead
        n_rounds += 1
        head = (tt[a], y) if full and a < K else None
    return {"a": a, "y": y, "accepts": accepts, "rounds": n_rounds,
            "margins": margins, "full_accept": full, "head": head,
            "rejections": rejections, "unreflected": unreflected}
