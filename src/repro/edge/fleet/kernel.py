"""Jitted x64 lax kernels for the fleet fast path.

Each kernel mirrors, op for op, one vectorized-numpy reference in
``repro.edge.allocation`` / ``EdgeRuntime.finish_round_sync``:

  * :func:`bandwidth_opt_widths_jit` — the barrier bisection of
    ``allocation.bandwidth_opt_widths`` (need(T) decreasing in T) as a
    branchless ``lax.while_loop`` doubling + ``fori_loop`` bisection.
  * :func:`energy_opt_widths_jit` — the KKT-λ bisection of
    ``allocation.energy_opt_widths`` (floored Σ widths increasing in λ).
  * :func:`sync_round_jit` — one fused sync round past the decision:
    Shannon capacity at the granted widths → realized finish → deadline
    verdict (drop mask + on-air byte fractions) → capped barrier /
    server-drain / idle energy / battery update.  Star topology (the
    tree aggregation path stays on the numpy backend).

Numerics: everything runs under ``jax.enable_x64(True)`` so
dtypes match the float64 references; results still differ from numpy by
float-op reassociation (XLA reductions are not numpy's pairwise sums,
``jnp.log2`` can be 1 ULP off ``np.log2``), which is why the jit
backend's contract is allclose-plus-identical-discrete-decisions, not
bitwise (``tests/test_fleet.py``), while the "exact" backend is bitwise.

The bisections are deliberately fixed-trip (``BISECT_ITERS``), not
tolerance-terminated: a fixed trip count keeps the loop shape static
for XLA and matches the scalar reference's iteration-for-iteration
bracket sequence.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.edge.allocation import BISECT_EPS, BISECT_ITERS

_GROW_MAX = 200   # bracket-doubling cap, as in bandwidth_opt_widths


@partial(jax.jit, static_argnames=("iters",))
def _bw_widths(bits, s, tc, budget, iters):
    def need(T):
        gap = T - tc
        safe = jnp.where(gap <= 0.0, 1.0, gap)
        return jnp.where(jnp.any(gap <= 0.0), jnp.inf,
                         jnp.sum(bits / (s * safe)))

    lo = jnp.max(tc)                     # infeasible: zero air time
    hi = jnp.maximum(2.0 * lo, lo + 1e-6)

    def grow_cond(carry):
        h, i = carry
        return (need(h) > budget) & (i < _GROW_MAX)

    def grow(carry):
        h, i = carry
        return h * 2.0, i + 1

    hi, _ = lax.while_loop(grow_cond, grow, (hi, 0))

    def bis(_, bracket):
        b_lo, b_hi = bracket
        mid = 0.5 * (b_lo + b_hi)
        ok = need(mid) <= budget
        return jnp.where(ok, b_lo, mid), jnp.where(ok, mid, b_hi)

    _, hi = lax.fori_loop(0, iters, bis, (lo, hi))
    w = bits / (s * jnp.maximum(hi - tc, BISECT_EPS))
    return w * (budget / jnp.sum(w))     # hand back the bracket slack

@partial(jax.jit, static_argnames=("iters",))
def _energy_widths(c, w_min, feas, budget, iters):
    n = c.shape[0]
    w_floor = jnp.where(feas, w_min, budget / n)
    total_floor = jnp.sum(w_floor)
    w_floor = jnp.where(total_floor > budget,
                        w_floor * (budget / total_floor), w_floor)
    sq = jnp.sqrt(jnp.maximum(c, 0.0))
    ssq = jnp.sum(sq)

    def floored(lam):
        return jnp.sum(jnp.maximum(w_floor, lam * sq))

    def bis(_, bracket):
        b_lo, b_hi = bracket
        mid = 0.5 * (b_lo + b_hi)
        ok = floored(mid) <= budget
        return jnp.where(ok, mid, b_lo), jnp.where(ok, b_hi, mid)

    lam, _ = lax.fori_loop(0, iters, bis,
                           (0.0, budget / jnp.maximum(ssq, 1e-300)))
    w = jnp.where(ssq > 0.0, jnp.maximum(w_floor, lam * sq),
                  jnp.maximum(w_floor, budget / n))
    tot = jnp.sum(w)
    return jnp.where(tot > 0.0, w * (budget / tot),
                     jnp.full_like(w, budget / n))

def _realloc_finish(f, tc, d, w, dropped):
    """Jit twin of :func:`repro.edge.events.reallocated_finish` in
    fixed shapes: survivors absorb the width each dropped client
    frees at its cutoff.  Non-dropped entries take a finite sentinel
    cut far beyond any real time (inf would poison the segment
    integrals), so the sorted breakpoint sweep keeps a static
    shape."""
    surv = ~dropped
    w_b = jnp.broadcast_to(w, f.shape)
    w_surv = jnp.sum(jnp.where(surv, w_b, 0.0))
    ok = (jnp.sum(dropped) > 0) & (w_surv > 0.0)
    w_safe = jnp.where(ok, w_surv, 1.0)
    big = 1e300
    cut = jnp.where(dropped, jnp.minimum(f, d), big)
    order = jnp.argsort(cut)
    ts = cut[order]
    c_seg = 1.0 + (jnp.cumsum(jnp.where(dropped, w_b, 0.0)[order])
                   / w_safe)
    integ = jnp.concatenate(
        [ts[:1], ts[0] + jnp.cumsum(c_seg[:-1] * jnp.diff(ts))])

    def cum(x):
        k = jnp.searchsorted(ts, x, side="right") - 1
        kk = jnp.clip(k, 0, ts.shape[0] - 1)
        return jnp.where(k >= 0,
                         integ[kk] + c_seg[kk] * (x - ts[kk]), x)

    target = cum(tc) + (f - tc)
    j = jnp.searchsorted(integ, target, side="right") - 1
    jj = jnp.clip(j, 0, ts.shape[0] - 1)
    fin = jnp.where(j >= 0,
                    ts[jj] + (target - integ[jj]) / c_seg[jj], target)
    fin = jnp.minimum(fin, f)      # never-later pin, as in numpy
    return jnp.where(ok & surv, fin, f)

@partial(jax.jit, static_argnames=("reallocate",))
def _sync_round(w, snr, t_comp, up_bytes, e_comp, deadline, tol,
                tx_power, srv_rate, idle_power, battery, bill_bytes,
                reallocate):
    # capacity at the granted widths (Channel.set_bandwidth), clamped
    # as in uplink_time_s
    rate = jnp.maximum(w * jnp.log2(1.0 + snr), 1e-6)
    t_up = 8.0 * up_bytes / rate
    time_s = t_comp + t_up
    e_tx = tx_power * t_up
    energy = e_comp + e_tx
    # deadline verdict (enforce_deadlines): the drop mask and the
    # byte fraction on the air before each cutoff
    dropped = time_s > deadline + tol
    air = jnp.clip(deadline - t_comp, 0.0, None)
    frac = jnp.where(
        dropped,
        jnp.where(t_up > 0.0,
                  jnp.minimum(air / jnp.maximum(t_up, 1e-300), 1.0),
                  0.0),
        1.0)
    # mid-round re-allocation (EdgeConfig.reallocate): each dropped
    # straggler's freed width re-lands on the surviving uploads from
    # its cutoff on, pulling survivor finishes — and the barrier —
    # earlier.  Drops, fractions and billing above are already fixed
    # at the granted widths, so the ledger/verdict is untouched.
    e_tx_plan = e_tx
    n_realloc = jnp.asarray(0)
    rate_eff = rate
    if reallocate:
        new_t = _realloc_finish(time_s, t_comp, deadline, w, dropped)
        n_realloc = jnp.sum((~dropped) & (new_t < time_s))
        # survivors absorbed the freed width mid-round: the realized
        # effective rate (same bits, less air time) is what the
        # server-drain air-time floor below must see — mirrors the
        # rate rescale in EdgeRuntime._maybe_reallocate
        air_old = time_s - t_comp
        air_new = new_t - t_comp
        improved = (~dropped) & (new_t < time_s)
        scale = jnp.where(improved & (air_new > 0.0),
                          air_old / jnp.maximum(air_new, 1e-300), 1.0)
        rate_eff = rate * scale
        e_tx = jnp.where(dropped, e_tx,
                         e_tx - tx_power * (time_s - new_t))
        time_s = new_t
    # star-topology finish (finish_round_sync): enforced barrier,
    # then the shared server slice drains the on-air bytes
    active = jnp.minimum(time_s, deadline)
    barrier = jnp.max(active)
    billed = bill_bytes * frac
    per = 8.0 * billed / jnp.maximum(rate_eff, 1e-6)
    t_round = jnp.maximum(
        barrier,
        jnp.maximum(jnp.max(per), 8.0 * jnp.sum(billed) / srv_rate))
    # capped battery drain (DeadlineVerdict.capped_spend_j) + idle
    # drain until the round closes
    idle = jnp.maximum(t_round - active, 0.0)
    e_comp_v = jnp.maximum(energy - e_tx_plan, 0.0)
    comp_frac = jnp.minimum(1.0,
                            deadline / jnp.maximum(t_comp, 1e-300))
    spend = e_comp_v * comp_frac + e_tx * frac + idle_power * idle
    battery_new = jnp.maximum(battery - spend, 0.0)
    return (barrier, t_round, jnp.sum(spend), jnp.sum(dropped),
            battery_new, frac, n_realloc)


def bandwidth_opt_widths_jit(bits, s, tc, budget: float,
                             iters: int = BISECT_ITERS) -> np.ndarray:
    """Jitted twin of :func:`repro.edge.allocation.bandwidth_opt_widths`."""
    with jax.enable_x64(True):
        w = _bw_widths(jnp.asarray(bits, jnp.float64),
                       jnp.asarray(s, jnp.float64),
                       jnp.asarray(tc, jnp.float64),
                       jnp.float64(budget), int(iters))
    return np.asarray(w, dtype=np.float64)


def energy_opt_widths_jit(c, w_min, feas, budget: float,
                          iters: int = BISECT_ITERS) -> np.ndarray:
    """Jitted twin of :func:`repro.edge.allocation.energy_opt_widths`."""
    with jax.enable_x64(True):
        w = _energy_widths(jnp.asarray(c, jnp.float64),
                           jnp.asarray(w_min, jnp.float64),
                           jnp.asarray(feas, bool),
                           jnp.float64(budget), int(iters))
    return np.asarray(w, dtype=np.float64)


def sync_round_jit(w, snr, t_comp, up_bytes, e_comp, deadline,
                   tol: float, tx_power: float, srv_rate: float,
                   idle_power: float, battery, bill_bytes=None,
                   reallocate: bool = False) -> dict:
    """One fused star-topology sync round past the decision.

    All per-client arrays align with the selected cohort; ``up_bytes``
    may be per-client (scenario workload shedding).  ``bill_bytes``
    (default ``up_bytes``) are the bytes the ledger meters — under
    shedding the plan is billed in full while the air time runs on the
    shed payload, exactly as ``finish_round_sync`` does.  ``reallocate``
    (static) re-lands freed straggler width on survivors mid-round.
    Returns a dict of host values: ``barrier_s``, ``t_round_s`` (barrier
    + server drain, pre-downlink), ``spend_j`` (cohort total incl. idle
    drain), ``n_dropped``, ``battery_j`` (updated per-client),
    ``tx_frac``, ``n_realloc`` (survivors whose finish moved earlier).
    """
    if bill_bytes is None:
        bill_bytes = up_bytes
    with jax.enable_x64(True):
        out = _sync_round(
            jnp.asarray(w, jnp.float64), jnp.asarray(snr, jnp.float64),
            jnp.asarray(t_comp, jnp.float64),
            jnp.asarray(up_bytes, jnp.float64),
            jnp.asarray(e_comp, jnp.float64),
            jnp.asarray(deadline, jnp.float64), jnp.float64(tol),
            jnp.float64(tx_power), jnp.float64(srv_rate),
            jnp.float64(idle_power), jnp.asarray(battery, jnp.float64),
            jnp.asarray(bill_bytes, jnp.float64), bool(reallocate))
    barrier, t_round, spend, n_dropped, battery_new, frac, n_realloc = out
    return {"barrier_s": float(barrier), "t_round_s": float(t_round),
            "spend_j": float(spend), "n_dropped": int(n_dropped),
            "battery_j": np.asarray(battery_new, dtype=np.float64),
            "tx_frac": np.asarray(frac, dtype=np.float64),
            "n_realloc": int(n_realloc)}
