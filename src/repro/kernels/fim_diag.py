"""Pallas TPU kernel: fused diagonal-Fisher accumulation (paper Eq. 9 + Γ).

Computes  new = ema*old + (1-ema) * mean_b(g[b]**2)  in one pass over a
leaf's per-example gradients, fusing square, batch-mean and EMA so the
gradients are read from HBM exactly once (the op is purely memory-bound:
2 flops/byte).

The gradients come in the leaf's own shape, (B, *leaf_shape), as
``vmap(grad)`` leaves them.  A leaf of rank >= 2 is viewed as (B, R, C),
C = leaf_shape[-1] and R the product of the middle dims: on TPU that view
is a bitcast whenever leaf_shape[-2] tiles by 8, so the per-example
gradients reach the kernel with no copy.  Flattening them to (B, D)
instead costs a transposing copy into (B, D) tiling (B padded to a
multiple of 8) and, to tile the batch, a pad to a multiple of the batch
block: three HBM passes over the largest array of the client step.  B is
the leading, untiled dim of the (bb, rb, cb) block, so it needs no
padding; the batch tail is summed through a static slice of the last
block.  A leaf of rank <= 1 (a bias, or a (B, D) matrix from a caller
that flattened its own) is viewed as (B, C) with B on the sublanes.

The grid runs over (R blocks, C blocks, B blocks) with the batch as the
*minor* axis, so the f32 accumulator block stays resident in the output
while the batch is reduced (TPU grids iterate minor-to-major
sequentially).  The diagonal rides as (R, C), or as a (1, C) row, so its
blocks keep two dims under vmap (the vmapped cohort step batches this
kernel over clients): the TPU compiler needs a block's last two dims to
tile as (8, 128) or span the array.  Blocks are sized on their lane- and
sublane-padded VMEM footprint, so a narrow leaf (C = 10 pads to 128
lanes) does not overrun the scoped VMEM.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_BYTES = 2 * 1024 * 1024  # padded f32 footprint of one gradient block
C_BLK = 2048                   # lane block cap, a multiple of 128
MIN_BB = 8                     # examples a 3-D block reduces, at least


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _blocks(B: int, R: int | None, C: int, itemsize: int):
    """Block shape of a (B, R, C) view, or of a (B, C) one when R is None."""
    sub = 32 // itemsize  # sublane tile: 8 rows of f32, 16 of bf16
    cb = C if C <= C_BLK else C_BLK
    row_bytes = _round_up(cb, 128) * 4  # the block is squared in f32
    if R is None:  # B is the sublane dim: a multiple of the tile, or all B
        if _round_up(B, sub) * row_bytes <= BLOCK_BYTES:
            return B, cb
        return BLOCK_BYTES // row_bytes // sub * sub, cb
    rows = BLOCK_BYTES // (MIN_BB * row_bytes)
    rb = R if _round_up(R, sub) <= rows else max(sub, rows // sub * sub)
    bb = max(1, min(B, BLOCK_BYTES // (_round_up(rb, sub) * row_bytes)))
    return bb, rb, cb


def _kernel(g_ref, old_ref, ema_ref, out_ref, *, nb: int, batch: int):
    b = pl.program_id(len(g_ref.shape) - 1)  # minor axis: batch blocks
    bb = g_ref.shape[0]
    tail = batch - (nb - 1) * bb  # rows of the last block inside B

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def accumulate(rows):
        g = g_ref[:rows].astype(jnp.float32)
        out_ref[...] += jnp.sum(g * g, axis=0).reshape(out_ref.shape)

    if tail == bb:
        accumulate(bb)
    else:  # rows past B in the last block hold whatever the buffer had
        pl.when(b < nb - 1)(lambda: accumulate(bb))
        pl.when(b == nb - 1)(lambda: accumulate(tail))

    @pl.when(b == nb - 1)
    def _finish():
        ema = ema_ref[0]
        meansq = out_ref[...] / batch
        out_ref[...] = ema * old_ref[...] + (1.0 - ema) * meansq


@functools.partial(jax.jit, static_argnames=("interpret",))
def fim_diag(grads, old_diag, ema, interpret: bool = False):
    """grads: (B, *leaf_shape); old_diag: leaf_shape (any dtype);
    ema: () -> leaf_shape f32.  A (B, D) input is the leaf_shape (D,)."""
    B, leaf = grads.shape[0], grads.shape[1:]
    C = leaf[-1] if leaf else 1
    if len(leaf) >= 2:
        R = math.prod(leaf[:-1])
        bb, rb, cb = _blocks(B, R, C, grads.dtype.itemsize)
        grid = (pl.cdiv(R, rb), pl.cdiv(C, cb), pl.cdiv(B, bb))
        g_view, diag_view = (B, R, C), (R, C)
        g_spec = pl.BlockSpec((bb, rb, cb), lambda r, c, b: (b, r, c))
        d_spec = pl.BlockSpec((rb, cb), lambda r, c, b: (r, c))
    else:
        bb, cb = _blocks(B, None, C, grads.dtype.itemsize)
        grid = (pl.cdiv(C, cb), pl.cdiv(B, bb))
        g_view, diag_view = (B, C), (1, C)
        g_spec = pl.BlockSpec((bb, cb), lambda c, b: (b, c))
        d_spec = pl.BlockSpec((1, cb), lambda c, b: (0, c))
    out = pl.pallas_call(
        functools.partial(_kernel, nb=grid[-1], batch=B),
        grid=grid,
        in_specs=[g_spec, d_spec,
                  pl.BlockSpec((1,), lambda *_: (0,))],
        out_specs=d_spec,
        out_shape=jax.ShapeDtypeStruct(diag_view, jnp.float32),
        interpret=interpret,
    )(grads.reshape(g_view),
      old_diag.astype(jnp.float32).reshape(diag_view),
      jnp.asarray(ema, jnp.float32).reshape(1))
    return out.reshape(leaf)
