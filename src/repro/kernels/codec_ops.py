"""Pallas TPU kernels for the wire codecs (fed/codecs.py hot path).

Two encode primitives sit on every upload's critical path:

  * ``int8_roundtrip`` — fused per-tensor symmetric int8 with stochastic
    rounding: scale, floor, uniform-compare, clip and dequantize run in
    one ``pallas_call`` over the flattened payload, so the tensor is
    read+written once instead of the unfused oracle's per-op passes.
    The rounding uniforms are drawn *outside* the kernel with the same
    ``jax.random.uniform`` stream as the oracle, and the per-tensor
    scale is precomputed by the caller (an exact f32 max reduction plus
    one division) and passed in — constant-divisor divisions compiled
    *inside* a kernel may round 1 ulp away from the eager oracle, while
    every op the kernel performs on the shared scale (dynamic divide,
    floor, compare, clip, multiply) is exact or correctly rounded, so
    kernel and oracle are bit-identical — the codec tests assert exact
    equality.

  * ``topk_select`` — threshold-select top-k without a global sort.  The
    magnitude order of nonnegative f32 values equals the integer order of
    their bit patterns, so bucketing on the top ``32 - TOPK_SHIFT`` bits
    of ``bitcast(|x|)`` is an order-preserving radix: pass 1 histograms
    the payload into ``TOPK_BUCKETS`` buckets, a 512-entry reversed
    cumsum picks the threshold bucket ``t`` (the coarsest bucket whose
    suffix count still reaches ``k``), pass 2 keeps every element above
    ``t`` plus the first ``k - count(>t)`` tie-bucket elements in index
    order (a running SMEM counter across the sequential grid).  Exactly
    ``k`` coordinates survive — the ``wire_bytes`` billing invariant —
    and both passes are O(n) streaming, versus the O(n log n) global
    ``jax.lax.top_k`` it replaces.

Both kernels see the flattened payload as a zero-padded (R, 128) array
tiled in (rows, 128) blocks with rows a multiple of 8, the shape the TPU
compiler requires of a block's last two dims; SMEM scalars are (1, 1)
for the same reason once vmap (the cohort step) adds a batch dim.  Mosaic lowers no
``cumsum``, so every count the top-k passes need runs on the MXU as a
matmul of 0/1 matrices (exact: the operands are 0/1 in bf16 and every
partial sum is an integer below 2**24 in the f32 accumulator):

  * the histogram factors bucket ``j = 32*hi + lo`` and contracts the
    one-hot ``hi`` rows against the one-hot ``lo`` rows over the
    elements, giving the (16, 32) joint count in one matmul per 8 rows;
  * the select pass's exclusive index-order rank is a lane prefix
    (tie row @ strictly-upper-triangular 128x128) plus the ties in the
    block's earlier rows (strictly-lower-triangular rows x rows @ tie,
    summed over lanes) plus the SMEM count of earlier blocks.

Dispatch (TPU-native / interpret / jnp-oracle) lives in ops.py; the
pure-jnp oracles with identical integer select logic live in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import TOPK_BUCKETS, TOPK_SHIFT

LANES = 128
INT8_ROWS = 1024    # (1024, 128) f32 blocks: 512 KiB per operand
TOPK_ROWS = 256     # select pass: a (256, 256) triangular matmul per block
LO_BITS = 5         # bucket j = 32*hi + lo: a (16, 32) joint histogram
HIST_LO = 1 << LO_BITS
HIST_HI = TOPK_BUCKETS // HIST_LO

_SEQUENTIAL = pltpu.CompilerParams(dimension_semantics=("arbitrary",))


def _bucket_of(x):
    """Order-preserving radix bucket of |x| (f32 -> int32 in [0, 512))."""
    bits = jax.lax.bitcast_convert_type(
        jnp.abs(x.astype(jnp.float32)), jnp.uint32)
    return (bits >> TOPK_SHIFT).astype(jnp.int32)


def _tile(flat, max_rows: int):
    """(n,) -> zero-padded (R, 128) and the row block ``br``: ``br`` is a
    multiple of 8 no larger than ``max_rows`` and R a multiple of br, so
    every block is whole (a partial tail block would feed the
    histogram whatever lies past the array)."""
    rows = pl.cdiv(flat.size, LANES)
    br = min(max_rows, 8 * pl.cdiv(rows, 8))
    total = br * pl.cdiv(rows, br) * LANES
    if total != flat.size:
        flat = jnp.pad(flat, (0, total - flat.size))
    return flat.reshape(-1, LANES), br


def _ones_where(cond):
    """bool -> exact 0/1 bf16 MXU operand."""
    return jnp.where(cond, 1.0, 0.0).astype(jnp.bfloat16)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Fused int8 stochastic-rounding round-trip
# ---------------------------------------------------------------------------
def _int8_kernel(x_ref, u_ref, s_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)
    scale = s_ref[0, 0]
    q = x / scale
    lo = jnp.floor(q)
    rnd = lo + (u_ref[...] < (q - lo)).astype(jnp.float32)
    out_ref[...] = jnp.clip(rnd, -127.0, 127.0) * scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def int8_roundtrip(x, u, scale, interpret: bool = False):
    """x: any-shape payload tensor; u: uniforms of the same shape;
    scale: () or (1,) per-tensor scale (see ref.int8_scale — computed by
    the caller so kernel and oracle consume one bit-identical value).
    Returns dequantize(quantize(x)) in f32, shaped like x.  Padded zeros
    round to zero and are sliced off."""
    x2, br = _tile(x.reshape(-1), INT8_ROWS)
    u2, _ = _tile(u.reshape(-1).astype(jnp.float32), INT8_ROWS)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    out = pl.pallas_call(
        _int8_kernel,
        grid=(x2.shape[0] // br,),
        in_specs=[spec, spec, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        interpret=interpret,
    )(x2, u2, jnp.asarray(scale, jnp.float32).reshape(1, 1))
    return out.reshape(-1)[:x.size].reshape(x.shape)


# ---------------------------------------------------------------------------
# Bucketed top-k threshold select
# ---------------------------------------------------------------------------
def _hist_kernel(x_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    hi_ids = jax.lax.broadcasted_iota(jnp.int32, (HIST_HI, LANES), 0)
    lo_ids = jax.lax.broadcasted_iota(jnp.int32, (HIST_LO, LANES), 0)

    def rows8(g, acc):
        bucket = _bucket_of(x_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :])
        hi, lo = bucket >> LO_BITS, bucket & (HIST_LO - 1)
        # element (r, c) -> column 128*r + c of both one-hot matrices
        a = jnp.concatenate(
            [_ones_where(hi[r:r + 1] == hi_ids) for r in range(8)], axis=1)
        b = jnp.concatenate(
            [_ones_where(lo[r:r + 1] == lo_ids) for r in range(8)], axis=1)
        return acc + _dot(a, b, ((1,), (1,)))        # (16, 32) counts

    counts = jax.lax.fori_loop(
        0, x_ref.shape[0] // 8, rows8,
        jnp.zeros((HIST_HI, HIST_LO), jnp.float32))
    out_ref[...] += counts.astype(jnp.int32)


def _select_kernel(x_ref, t_ref, need_ref, out_ref, seen_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        seen_ref[0] = 0

    x = x_ref[...]
    bucket = _bucket_of(x)
    t = t_ref[0, 0]
    tie = bucket == t
    tie01 = _ones_where(tie)
    rows = x.shape[0]
    # exclusive global index-order rank among tie-bucket elements
    lane_before = _ones_where(
        jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
        < jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    row_before = _ones_where(
        jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
        < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0))
    in_row = _dot(tie01, lane_before, ((1,), (0,)))
    rows_above = jnp.sum(_dot(row_before, tie01, ((1,), (0,))),
                         axis=1, keepdims=True)
    rank = seen_ref[0] + (in_row + rows_above).astype(jnp.int32)
    keep = (bucket > t) | (tie & (rank < need_ref[0, 0]))
    out_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))
    seen_ref[0] += jnp.sum(tie.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_select(flat, k, interpret: bool = False):
    """Zero all but the ``k`` largest-|x| entries of a 1-D payload.

    Ties on the threshold bucket break by index order (lowest index
    wins), so exactly ``k`` coordinates survive for any 1 <= k <= n."""
    # padded zeros land in bucket 0 *after* every real element in index
    # order, and need <= count(real bucket-0) whenever k <= n, so padding
    # can neither shift the threshold nor get selected
    x2, br = _tile(flat, TOPK_ROWS)
    grid = (x2.shape[0] // br,)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))

    hist = pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=pl.BlockSpec((HIST_HI, HIST_LO), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((HIST_HI, HIST_LO), jnp.int32),
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(x2).reshape(TOPK_BUCKETS)

    # threshold bucket: coarsest t whose suffix count still reaches k
    k = jnp.asarray(k, jnp.int32)
    ge = jnp.cumsum(hist[::-1])[::-1]  # ge[t] = count(bucket >= t)
    t = jnp.max(jnp.where(
        ge >= k, jnp.arange(TOPK_BUCKETS, dtype=jnp.int32), 0))
    need = k - (ge[t] - hist[t])       # tie-bucket quota

    out = pl.pallas_call(
        _select_kernel,
        grid=grid,
        in_specs=[
            spec,
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, flat.dtype),
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        compiler_params=_SEQUENTIAL,
        interpret=interpret,
    )(x2, t.reshape(1, 1), need.reshape(1, 1))
    return out.reshape(-1)[:flat.size]
