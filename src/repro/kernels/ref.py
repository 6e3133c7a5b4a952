"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

The codec oracles (``int8_roundtrip_ref``, ``topk_select_ref``) are also
the *default* encode path on non-TPU backends (ops.py mode "auto"), so
they are bit-exact re-statements of the historical codec semantics, not
approximations."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# Radix-bucket geometry shared by topk_select_ref and the Pallas kernel
# (codec_ops.py): nonnegative f32 magnitudes order exactly like their bit
# patterns, so the top 32 - TOPK_SHIFT = 10 bits (sign always 0, 8
# exponent bits, 1 mantissa bit) are an order-preserving radix with
# 2**10 / 2 = 512 reachable buckets and a tie band narrower than 1.5x.
TOPK_BUCKETS = 512
TOPK_SHIFT = 22


def fim_diag_ref(grads, old_diag, ema: float):
    """grads: (B, *shape) per-example (or per-microbatch) gradients;
    old_diag: shape, f32 EMA state.  Returns ema*old + (1-ema)*mean(g²)
    over the batch axis."""
    meansq = jnp.mean(jnp.square(grads.astype(jnp.float32)), axis=0)
    return ema * old_diag.astype(jnp.float32) + (1.0 - ema) * meansq


def vlbfgs_gram_ref(basis):
    """basis: (n, D) rows [s_0..s_{m-1}, y_0..y_{m-1}, g].
    Returns (n, n) Gram matrix in f32."""
    b = basis.astype(jnp.float32)
    return b @ b.T


def int8_scale(x):
    """The per-tensor symmetric int8 scale, max|x|/127 (floored at
    1e-12/127 for all-zero tensors) — the exact expression of the
    historical ``codecs.quantize_tree``.  Computed once by the dispatch
    wrapper and shared by kernel and oracle: f32 max is order-exact and
    the single division is evaluated in one place, so both paths consume
    a bit-identical scale."""
    a = x.astype(jnp.float32)
    return jnp.maximum(jnp.max(jnp.abs(a)), 1e-12) / 127.0


def int8_roundtrip_ref(x, u, scale=None):
    """Per-tensor symmetric int8 with stochastic rounding, dequantized.

    x: payload tensor; u: uniforms of x's shape (the caller owns the PRNG
    stream so kernel and oracle consume identical draws).  Matches the
    historical ``codecs.quantize_tree``/``dequantize_tree`` pair bit-for-
    bit: the int8 cast is elided because the clipped rounded value is
    already integral in [-127, 127]."""
    a = x.astype(jnp.float32)
    s = int8_scale(x) if scale is None else scale
    q = a / s
    lo = jnp.floor(q)
    rnd = lo + (u.astype(jnp.float32) < (q - lo)).astype(jnp.float32)
    return jnp.clip(rnd, -127.0, 127.0) * s


def topk_select_ref(flat, k):
    """Bucketed threshold select: zero all but the k largest-|x| entries
    of a 1-D payload — same integer logic as codec_ops.topk_select (bit-
    identical keep masks), no global sort.  Threshold-bucket ties break
    by index order, so exactly k coordinates survive."""
    bits = jax.lax.bitcast_convert_type(
        jnp.abs(flat.astype(jnp.float32)), jnp.uint32)
    bucket = (bits >> TOPK_SHIFT).astype(jnp.int32)
    hist = jnp.zeros((TOPK_BUCKETS,), jnp.int32).at[bucket].add(1)
    k = jnp.asarray(k, jnp.int32)
    ge = jnp.cumsum(hist[::-1])[::-1]  # ge[t] = count(bucket >= t)
    t = jnp.max(jnp.where(
        ge >= k, jnp.arange(TOPK_BUCKETS, dtype=jnp.int32), 0))
    need = k - (ge[t] - hist[t])
    tie = (bucket == t).astype(jnp.int32)
    rank = jnp.cumsum(tie) - tie       # exclusive index-order rank
    keep = (bucket > t) | ((tie == 1) & (rank < need))
    return jnp.where(keep, flat, jnp.zeros_like(flat))


def flash_attention_ref(q, k, v, causal: bool = True, window: int = 0):
    """q: (B, H, S, hd); k, v: (B, KV, S, hd); GQA by head folding.
    f32 softmax; returns (B, H, S, hd) in q.dtype."""
    B, H, S, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qf = q.reshape(B, KV, G, S, hd).astype(jnp.float32) * hd ** -0.5
    scores = jnp.einsum("bkgqh,bksh->bkgqs", qf, k.astype(jnp.float32))
    pos = jnp.arange(S)
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= pos[:, None] >= pos[None, :]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bksh->bkgqh", probs, v.astype(jnp.float32))
    return out.reshape(B, H, S, hd).astype(q.dtype)
