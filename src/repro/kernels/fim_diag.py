"""Pallas TPU kernel: fused diagonal-Fisher accumulation (paper Eq. 9 + Γ).

Computes  new = ema*old + (1-ema) * mean_b(g[b, :]**2)  in one pass over the
(B, D) per-example-gradient matrix, fusing square, batch-mean and EMA so the
gradient tile is read from HBM exactly once (the op is purely memory-bound:
2 flops/byte).  Tiled (B_BLK, D_BLK) over VMEM with the batch dimension as
the *minor* grid axis so the f32 accumulator tile stays resident while the
batch is reduced (TPU grids iterate minor-to-major sequentially).  The
diagonal rides as a (1, D) row so its blocks keep two dims under vmap
(the vmapped cohort step batches this kernel over clients): the TPU
compiler needs a block's last two dims to tile as (8, 128) or span the
array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

D_BLK = 2048
B_BLK = 256


def _kernel(g_ref, old_ref, ema_ref, out_ref, *, nb: int, batch: int):
    b = pl.program_id(1)  # minor axis: batch tiles reduce into out_ref

    @pl.when(b == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    g = g_ref[...].astype(jnp.float32)
    out_ref[...] += jnp.sum(g * g, axis=0, keepdims=True)

    @pl.when(b == nb - 1)
    def _finish():
        ema = ema_ref[0]
        meansq = out_ref[...] / batch
        out_ref[...] = ema * old_ref[...] + (1.0 - ema) * meansq


@functools.partial(jax.jit, static_argnames=("interpret",))
def fim_diag(grads, old_diag, ema, interpret: bool = False):
    """grads: (B, D); old_diag: (D,) f32; ema: () f32 -> (D,) f32."""
    B, D = grads.shape
    db = min(D_BLK, D)
    bb = min(B_BLK, B)
    nd = pl.cdiv(D, db)
    nb = pl.cdiv(B, bb)
    # zero-pad tail tiles explicitly (as vlbfgs.gram does): padded rows
    # add 0 to the g² sum (the mean still divides by the true B) and the
    # padded diag tail is sliced off below
    if B % bb or D % db:
        grads = jnp.pad(grads, ((0, nb * bb - B), (0, nd * db - D)))
    old_diag = old_diag.astype(jnp.float32).reshape(1, D)
    if D % db:
        old_diag = jnp.pad(old_diag, ((0, 0), (0, nd * db - D)))
    ema = jnp.asarray(ema, jnp.float32).reshape(1)
    out = pl.pallas_call(
        functools.partial(_kernel, nb=nb, batch=B),
        grid=(nd, nb),
        in_specs=[
            pl.BlockSpec((bb, db), lambda d, b: (b, d)),
            pl.BlockSpec((1, db), lambda d, b: (0, d)),
            pl.BlockSpec((1,), lambda d, b: (0,)),
        ],
        out_specs=pl.BlockSpec((1, db), lambda d, b: (0, d)),
        out_shape=jax.ShapeDtypeStruct((1, nd * db), jnp.float32),
        interpret=interpret,
    )(grads, old_diag, ema)
    return out[0, :D]
