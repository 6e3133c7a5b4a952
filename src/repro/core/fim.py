"""Diagonal empirical Fisher Information Matrix (paper Sec. IV-A, Eq. 9).

The paper approximates the Hessian with the Fisher information
E[∇f ∇fᵀ], then keeps only the diagonal (Γ, the diagonalization step after
Eq. 9) so each client stores/communicates O(d) instead of O(d²).

Two estimation modes (cfg.fim_mode):
  * "per_example" — exact Eq. 9 diagonal: vmap per-example gradients, mean of
    squares.  Faithful to the paper; used for the paper-scale CNN models.
  * "microbatch"  — mean of squared *microbatch* gradients, produced for free
    by gradient accumulation.  Used for LLM-scale configs where per-example
    Jacobians are infeasible (documented deviation, DESIGN.md §3).

Both feed the same smoothing y_t = (Γ̄ + λI) s_t (Alg. 1 line 8), where Γ̄ is
the client-aggregated FIM.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops


class FimState(NamedTuple):
    diag: object      # pytree like params — EMA of the diagonal Fisher
    steps: jax.Array  # () int32


def init(params, dtype=jnp.float32) -> FimState:
    return FimState(
        diag=jax.tree.map(lambda p: jnp.zeros(p.shape, dtype), params),
        steps=jnp.zeros((), jnp.int32),
    )


def _leaf_diag(g, kernels: str):
    """(B, *leaf_shape) per-example gradients -> leaf_shape mean of
    squares, via the fused Pallas op (repro.kernels.ops).  The gradients
    go in the shape ``vmap(grad)`` gives them: the kernel views a leaf as
    (B, R, C) in place, where a (B, D) flatten would cost a transposing
    copy and pads of B on every large leaf.  With old=0 and ema=0 the
    fused Γ update reduces to exactly mean_b g² — bit-identical to the
    inline jnp expression on the oracle path."""
    zeros = jnp.zeros(g.shape[1:], jnp.float32)
    return kernel_ops.fim_diag_update(g, zeros, 0.0, mode=kernels)


def per_example_diag(per_example_loss: Callable, params, xs, ys,
                     kernels: str = "off"):
    """Exact diagonal empirical Fisher: mean over the batch of squared
    per-example gradients.  ``per_example_loss(params, x, y) -> scalar``.

    ``kernels`` routes the square+mean through the fused Pallas op
    (repro.kernels.ops.fim_diag_update), one call per leaf on the leaf's
    (B, *shape) gradients as they are; "off"/non-TPU "auto" resolve to
    the bit-identical jnp oracle."""
    grads = jax.vmap(lambda x, y: jax.grad(per_example_loss)(params, x, y))(xs, ys)
    return jax.tree.map(lambda g: _leaf_diag(g, kernels), grads)


def microbatch_diag(grad, kernels: str = "off"):
    """Squared (micro)batch gradient — one term of the accumulation mean
    (a B=1 instance of the same fused Γ op, on a (1, *shape) view)."""
    return jax.tree.map(lambda g: _leaf_diag(g[None], kernels), grad)


def update(state: FimState, new_diag, ema: float) -> FimState:
    """EMA accumulation of the Fisher diagonal with bias-corrected warmup."""
    def upd(old, new):
        mixed = ema * old + (1.0 - ema) * new.astype(old.dtype)
        return jnp.where(state.steps == 0, new.astype(old.dtype), mixed)

    return FimState(
        diag=jax.tree.map(upd, state.diag, new_diag),
        steps=state.steps + 1,
    )


def mean_diag(state: FimState) -> jax.Array:
    """Mean of the Fisher diagonal across all parameters (f32 scalar)."""
    sums = [jnp.sum(d) for d in jax.tree.leaves(state.diag)]
    cnt = sum(d.size for d in jax.tree.leaves(state.diag))
    return jnp.sum(jnp.stack(sums)) / jnp.float32(max(cnt, 1))


def smooth_y(state: FimState, s, damping: float, rel_damping: float = 0.1):
    """Paper Alg. 1 line 8: y_t = B̄_t s_t with B̄ = Γ̄ + λ_t I.

    λ_t = damping + rel_damping·mean(Γ̄) keeps B̄ ⪰ λ_t I (Assumption 1's
    lower bound — Lemma 1's θ₁ > 0) while also bounding the *relative*
    amplification of the implied preconditioner:  1/(Γ_ii + λ_t) ≤
    1/(rel_damping·mean Γ̄), i.e. Lemma 1's θ₂ made operational.  Without the
    relative term, near-zero Fisher entries (dead ReLUs at init) dominate
    the direction and the method stalls inside its trust region."""
    lam = damping + rel_damping * mean_diag(state)
    return jax.tree.map(
        lambda d, si: ((d + lam) * si.astype(jnp.float32)).astype(si.dtype),
        state.diag, s,
    )
