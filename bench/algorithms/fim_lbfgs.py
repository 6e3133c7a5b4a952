"""Plain reference of Algorithm 1 (FIM-L-BFGS) of arXiv:2110.07567.

One round, as the paper states it:

1. every sampled client k computes its mean loss, gradient g_k and the
   exact diagonal empirical Fisher Gamma_k (Eq. 9: the mean of squared
   per-example gradients) over its whole local data;
2. the server takes the n_k-weighted means g and Gamma;
3. it keeps an EMA of Gamma (the first round takes Gamma itself),
   D = ema * D + (1 - ema) * Gamma;
4. the direction is p = -H g by the textbook two-loop recursion over the
   last m pairs (s_i, y_i), scaled by gamma = s.y / y.y of the newest;
5. a trust region clips the step: s = lr * min(1, max_step / (lr |p|)) p,
   and w <- w + s;
6. FIM smoothing: y = (D + lambda) s with
   lambda = damping + rel_damping * mean(D);
7. the pair is kept only if s.y > eps |s| |y| (the curvature guard).

Everything runs on flat float32 vectors, with products summed
elementwise rather than through the matrix unit, so a TPU adds no
rounding of its own.  The client work comes from the model family's
reference (``client_stats``), in the precision asked for.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dot(a, b):
    return jnp.sum(a * b)


def _flat(tree):
    return jnp.concatenate([jnp.ravel(x) for x in jax.tree.leaves(tree)])


def _unflat(vec, like):
    leaves, treedef = jax.tree.flatten(like)
    out, i = [], 0
    for leaf in leaves:
        out.append(vec[i:i + leaf.size].reshape(leaf.shape))
        i += leaf.size
    return jax.tree.unflatten(treedef, out)


def _two_loop(pairs, g):
    """-H g over ``pairs`` = [(s, y, rho)], oldest first."""
    q = g
    alphas = []
    for s, y, rho in reversed(pairs):
        a = rho * _dot(s, q)
        q = q - a * y
        alphas.append(a)
    if pairs:
        s, y, _ = pairs[-1]
        yy = _dot(y, y)
        gamma = jnp.where(yy > 1e-20, _dot(s, y) / yy, 1.0)
    else:
        gamma = 1.0
    r = gamma * q
    for (s, y, rho), a in zip(pairs, reversed(alphas), strict=True):
        b = rho * _dot(y, r)
        r = r + (a - b) * s
    return -r


@jax.jit
def _server_step(w, g, gamma, fisher, steps, pairs_s, pairs_y, hyper):
    lr, max_step, damping, rel_damping, ema, eps = hyper
    fisher = jnp.where(steps == 0, gamma, ema * fisher + (1.0 - ema) * gamma)
    pairs = []
    for s, y in zip(pairs_s, pairs_y, strict=True):
        sy = _dot(y, s)
        pairs.append((s, y, jnp.where(jnp.abs(sy) > 1e-20, 1.0 / sy, 0.0)))
    p = _two_loop(pairs, g)
    pn = jnp.sqrt(_dot(p, p)) * lr
    scale = jnp.where(max_step > 0,
                      jnp.minimum(1.0, max_step / jnp.maximum(pn, 1e-12)),
                      1.0)
    s = lr * scale * p
    lam = damping + rel_damping * jnp.mean(fisher)
    y = (fisher + lam) * s
    ok = _dot(s, y) > eps * jnp.sqrt(_dot(s, s)) * jnp.sqrt(_dot(y, y))
    return w + s, fisher, s, y, ok


def hyper(traffic: dict):
    return tuple(jnp.float32(traffic[k]) for k in (
        "second_order_lr", "max_step_norm", "fim_damping", "rel_damping",
        "fim_ema", "curvature_eps"))


FAULTS = {
    # half of each client's batch left out, the mean taken over the rest
    "half_batch",
    # each client's Fisher diagonal doubled where it is produced
    "fisher_x2",
    # the history stops taking pairs once it holds m: the ring never wraps
    "stale_history",
}


def reference_rounds(family, cfg: dict, traffic: dict, params0, cohorts,
                     dtype=jnp.float32, fault: str | None = None):
    """Follow the program's first rounds.

    ``cohorts``: one list per round of the sampled clients' (x, y) host
    arrays, in the order the program took them.  ``dtype`` is the
    precision of the client work (float32 for the reference, lower for
    the control); ``fault`` plants one of ``FAULTS``.

    Returns a dict of host arrays: ``loss`` per round, the first round's
    aggregated gradient ``grad1`` and Fisher diagonal ``fisher1`` (trees
    like the parameters), and the parameters after the last round,
    ``params``."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {sorted(FAULTS)}")
    m = int(traffic["lbfgs_m"])
    hp = hyper(traffic)
    w = _flat(params0)
    fisher = jnp.zeros_like(w)
    history: list = []
    out: dict = {"loss": []}
    for t, cohort in enumerate(cohorts):
        g_sum = jnp.zeros_like(w)
        f_sum = jnp.zeros_like(w)
        n_sum = 0.0
        losses = []
        params = _unflat(w, params0)
        for x, y in cohort:
            keep = len(y) // 2 if fault == "half_batch" else len(y)
            loss, g, f = family.client_stats(params, cfg, x[:keep], y[:keep],
                                             dtype=dtype)
            if fault == "fisher_x2":
                f = jax.tree.map(lambda a: 2.0 * a, f)
            n = float(len(y))
            g_sum = g_sum + n * _flat(g)
            f_sum = f_sum + n * _flat(f)
            n_sum += n
            losses.append(float(loss))
        g_bar, f_bar = g_sum / n_sum, f_sum / n_sum
        out["loss"].append(float(np.mean(losses)))
        if t == 0:
            out["grad1"] = jax.tree.map(np.asarray, _unflat(g_bar, params0))
            out["fisher1"] = jax.tree.map(np.asarray, _unflat(f_bar, params0))
        w, fisher, s, y, ok = _server_step(
            w, g_bar, f_bar, fisher, jnp.int32(t),
            tuple(h[0] for h in history), tuple(h[1] for h in history), hp)
        if bool(ok) and not (fault == "stale_history" and len(history) == m):
            history = (history + [(s, y)])[-m:]
    out["params"] = jax.tree.map(np.asarray, _unflat(w, params0))
    return out
