"""The numbers that decide ``correct``, each against its limit.

A training cell compares what the program's first rounds produced with
the plain reference following the same rounds (the cell's
``reference_rounds``):

* ``loss1``: the relative gap of round 1's mean client loss;
* ``loss2``: the largest relative gap of rounds 1 and 2's losses, before
  the trajectories part ways;
* ``loss``: the largest relative gap of a followed round's loss;
* ``grad1``: the aggregated gradient the optimizer received in round 1;
* ``fisher1``: the aggregated Fisher diagonal it received in round 1;
* ``delta``: the change of the parameters over the followed rounds.

A tree is compared by its worst leaf: the gap between the program's norm
of the leaf and the reference's, over the reference's norm of that leaf
or of the median leaf, whichever is larger (some leaves are all but
zero).  ``delta`` leaves out leaves whose reference gradient in round 1
is under a thousandth of the median leaf's, which only round-off moves.
"""
from __future__ import annotations

import math

import numpy as np

NUMBERS = ("loss1", "loss2", "loss", "grad1", "fisher1", "delta")
QUIET_LEAF = 1e-3


def leaf_norms(tree) -> np.ndarray:
    import jax
    return np.asarray([float(np.linalg.norm(np.asarray(x, np.float64)))
                       for x in jax.tree.leaves(tree)])


def worst_leaf_gap(program, reference, keep=None) -> float:
    p, r = leaf_norms(program), leaf_norms(reference)
    if len(p) != len(r):
        return math.inf
    scale = np.maximum(r, np.median(r))
    gaps = np.abs(p - r) / scale
    if keep is not None:
        gaps = gaps[np.asarray(keep)]
    return float(np.max(gaps)) if np.all(np.isfinite(gaps)) else math.inf


def loss_gap(program, reference) -> float:
    p, r = np.asarray(program, np.float64), np.asarray(reference, np.float64)
    if p.shape != r.shape:
        return math.inf
    gaps = np.abs(p - r) / np.abs(r)
    return float(np.max(gaps)) if np.all(np.isfinite(gaps)) else math.inf


def readings(program: dict, reference: dict, params0) -> dict:
    """Every compared number.  ``program`` and ``reference`` hold
    ``loss`` (per round followed), ``grad1``, ``fisher1`` and ``params``
    (after the last round followed); ``params0`` the weights both
    started from."""
    import jax
    g_ref = leaf_norms(reference["grad1"])
    keep = g_ref >= QUIET_LEAF * np.median(g_ref)

    def delta(tree):
        return jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                            - np.asarray(b, np.float64), tree, params0)

    return {
        "loss1": loss_gap(program["loss"][:1], reference["loss"][:1]),
        "loss2": loss_gap(program["loss"][:2], reference["loss"][:2]),
        "loss": loss_gap(program["loss"], reference["loss"]),
        "grad1": worst_leaf_gap(program["grad1"], reference["grad1"]),
        "fisher1": worst_leaf_gap(program["fisher1"], reference["fisher1"]),
        "delta": worst_leaf_gap(delta(program["params"]),
                                 delta(reference["params"]), keep),
    }


def judge(values: dict, limits: dict) -> tuple[bool, list]:
    """-> (correct, [(name, value, limit, ok)]).  A number with no limit
    is reported and not compared; a missing or non-finite one fails."""
    rows = []
    for name in NUMBERS:
        value = values.get(name, math.nan)
        limit = limits.get(name)
        ok = True if limit is None else bool(value <= limit)
        rows.append((name, value, limit, ok))
    return all(ok for *_, ok in rows), rows
