"""Vector-free L-BFGS (paper Sec. IV-A; two-loop recursion of [44]).

The classical two-loop recursion interleaves O(d) dot products with O(d)
axpys m times.  The *vector-free* formulation (Chen et al., NeurIPS 2014 —
the algorithm the paper's Alg. 1 line 6 invokes) instead expresses the
direction in the basis  b = [s_0..s_{m-1}, y_0..y_{m-1}, g]  and runs the two
loops on the (2m+1)x(2m+1) Gram matrix of that basis.  In the federated
setting this is the whole point: with parameters (and hence s_i, y_i, g)
sharded across devices, the Gram matrix costs one fused pass over the shards
plus a (2m+1)² scalar all-reduce — the O(m²) communication term of
Theorem 3 — and the direction is a local linear combination (O(d), no
communication).

History is a functional circular buffer: pytrees with a leading ``m`` dim,
a write index and a live count, so the whole optimizer jits and shards.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops


class History(NamedTuple):
    s: object            # pytree, leaves (m, ...) — parameter deltas
    y: object            # pytree, leaves (m, ...) — FIM-smoothed grad deltas
    idx: jax.Array       # () int32 — next write slot
    count: jax.Array     # () int32 — number of live pairs (<= m)


def init(params, m: int, dtype=None) -> History:
    def alloc(p):
        return jnp.zeros((m,) + p.shape, dtype or p.dtype)

    return History(
        s=jax.tree.map(alloc, params),
        y=jax.tree.map(alloc, params),
        idx=jnp.zeros((), jnp.int32),
        count=jnp.zeros((), jnp.int32),
    )


def push(h: History, s, y) -> History:
    new_s = jax.tree.map(lambda b, v: b.at[h.idx].set(v.astype(b.dtype)), h.s, s)
    new_y = jax.tree.map(lambda b, v: b.at[h.idx].set(v.astype(b.dtype)), h.y, y)
    m = jax.tree.leaves(h.s)[0].shape[0]
    return History(
        s=new_s, y=new_y,
        idx=(h.idx + 1) % m,
        count=jnp.minimum(h.count + 1, m),
    )


# ---------------------------------------------------------------------------
# Gram matrix
# ---------------------------------------------------------------------------
def gram_matrix(h: History, g):
    """M[i,j] = <b_i, b_j> for b = [s_0.., y_0.., g]; f32 accumulation.

    Pure-jnp path; repro/kernels/vlbfgs.py is the blocked Pallas TPU kernel
    with identical semantics (tests assert allclose against this)."""
    m = jax.tree.leaves(h.s)[0].shape[0]
    n = 2 * m + 1

    def dots(a, b):
        # Contract over every trailing (parameter) dim in one dot_general,
        # f32-accumulated.  No reshape(m, -1): merging sharded dims would
        # force GSPMD to all-gather the whole history (hundreds of GB at
        # LLM scale); contracting the dims in place keeps each shard local
        # and reduces with a scalar-sized all-reduce.
        dims = tuple(range(1, a.ndim))
        return jax.lax.dot_general(
            a, b, ((dims, dims), ((), ())), preferred_element_type=jnp.float32)

    def leaf_gram(sb, yb, gl):
        s2 = sb
        y2 = yb
        g2 = gl[None]
        ss, sy, sg = dots(s2, s2), dots(s2, y2), dots(s2, g2)
        yy, yg = dots(y2, y2), dots(y2, g2)
        gg = dots(g2, g2)
        top = jnp.concatenate([ss, sy, sg], axis=1)
        mid = jnp.concatenate([sy.T, yy, yg], axis=1)
        bot = jnp.concatenate([sg.T, yg.T, gg], axis=1)
        return jnp.concatenate([top, mid, bot], axis=0)

    grams = jax.tree.map(leaf_gram, h.s, h.y, g)
    return sum(jax.tree.leaves(grams), jnp.zeros((n, n), jnp.float32))


# ---------------------------------------------------------------------------
# Two-loop recursion in Gram space
# ---------------------------------------------------------------------------
def direction_coeffs(M, idx, count, m: int):
    """Coefficients δ with  H·g = Σ_j δ_j b_j  (so the step is p = -Σ δ b).

    Slots are visited newest-to-oldest in the first loop and oldest-to-newest
    in the second, honouring the circular buffer.  Empty slots contribute
    nothing (ρ=0), so with count==0 this degrades to δ = e_g (steepest
    descent), matching L-BFGS-with-empty-memory."""
    n = 2 * m + 1
    delta = jnp.zeros((n,), jnp.float32).at[2 * m].set(1.0)

    def slot(age):  # age 0 = newest
        return (idx - 1 - age) % m

    def rho_of(i):
        return jnp.where(
            jnp.abs(M[i, m + i]) > 1e-20, 1.0 / M[i, m + i], 0.0
        )

    def loop1(age, carry):
        delta, alphas = carry
        i = slot(age)
        live = age < count
        rho = rho_of(i) * live
        alpha = rho * jnp.dot(M[i], delta)          # <s_i, q>
        delta = delta.at[m + i].add(-alpha)
        alphas = alphas.at[age].set(alpha)
        return delta, alphas

    delta, alphas = jax.lax.fori_loop(
        0, m, loop1, (delta, jnp.zeros((m,), jnp.float32))
    )

    newest = slot(0)
    sy = M[newest, m + newest]
    yy = M[m + newest, m + newest]
    gamma = jnp.where((count > 0) & (yy > 1e-20), sy / yy, 1.0)
    delta = delta * gamma

    def loop2(k, delta):
        age = count - 1 - k  # oldest first among live entries
        i = slot(age)
        live = (age >= 0) & (age < count)
        rho = rho_of(i) * live
        beta = rho * jnp.dot(M[m + i], delta)       # <y_i, r>
        alpha = jnp.where(live, alphas[age], 0.0)
        return delta.at[i].add(alpha - beta)

    delta = jax.lax.fori_loop(0, m, loop2, delta)
    return delta


def combine(h: History, g, delta):
    """p = -(Σ_i δ_i s_i + Σ_i δ_{m+i} y_i + δ_{2m} g): local O(d), no comm."""
    m = jax.tree.leaves(h.s)[0].shape[0]
    ds, dy, dg = delta[:m], delta[m:2 * m], delta[2 * m]

    def leaf(sb, yb, gl):
        # f32 accumulation without casting the (m, ...) history to f32
        acc = jnp.einsum("m,m...->...", ds, sb,
                         preferred_element_type=jnp.float32)
        acc = acc + jnp.einsum("m,m...->...", dy, yb,
                               preferred_element_type=jnp.float32)
        acc = acc + dg * gl.astype(jnp.float32)
        return (-acc).astype(gl.dtype)

    return jax.tree.map(leaf, h.s, h.y, g)


def gram_basis(h: History, g):
    """The (2m+1, D) basis [s_0.., y_0.., g] the Gram kernel reads,
    materialized by raveling every history leaf.

    This is the single-host/paper-scale fast path — the reshape+concat
    that ``gram_matrix`` deliberately avoids is exactly what lets one
    pallas_call read each basis element once.  At LLM scale (sharded
    history) keep ``kernels="off"``: merging sharded dims would force an
    all-gather (see gram_matrix)."""
    def rows(tree):
        return jnp.concatenate(
            [leaf.reshape(leaf.shape[0], -1).astype(jnp.float32)
             for leaf in jax.tree.leaves(tree)], axis=1)

    gflat = jnp.concatenate(
        [leaf.ravel().astype(jnp.float32) for leaf in jax.tree.leaves(g)])
    return jnp.concatenate([rows(h.s), rows(h.y), gflat[None]], axis=0)


def direction(h: History, g, kernels: str = "off"):
    """Full VL-BFGS step: p = -H_t g (Alg. 1 line 6).

    ``kernels`` ("auto" | "on" | "off", FimLbfgsConfig.kernels) routes
    the Gram matrix through repro.kernels.ops.vlbfgs_gram; "off" (the
    default, and the right setting for sharded LLM-scale history) keeps
    the per-leaf all-gather-free ``gram_matrix`` path."""
    m = jax.tree.leaves(h.s)[0].shape[0]
    if kernel_ops.resolve(kernels) == "oracle":
        M = gram_matrix(h, g)
    else:
        M = kernel_ops.vlbfgs_gram(gram_basis(h, g), mode=kernels)
    delta = direction_coeffs(M, h.idx, h.count, m)
    return combine(h, g, delta)


def reference_two_loop(s_list, y_list, g):
    """Textbook O(d)-vector two-loop recursion (oracle for tests).

    s_list/y_list: python lists of flat f64 arrays, oldest first."""
    import numpy as np

    q = np.asarray(g, dtype=np.float64).copy()
    alphas = []
    rhos = [1.0 / float(np.dot(y, s))
            for s, y in zip(s_list, y_list, strict=True)]
    for s, y, rho in zip(reversed(s_list), reversed(y_list), reversed(rhos),
                         strict=True):
        a = rho * float(np.dot(s, q))
        q -= a * np.asarray(y, np.float64)
        alphas.append(a)
    if s_list:
        gamma = float(np.dot(s_list[-1], y_list[-1]) / np.dot(y_list[-1], y_list[-1]))
    else:
        gamma = 1.0
    r = gamma * q
    for (s, y, rho), a in zip(zip(s_list, y_list, rhos, strict=True),
                              reversed(alphas), strict=True):
        b = rho * float(np.dot(y, r))
        r += (a - b) * np.asarray(s, np.float64)
    return -r
