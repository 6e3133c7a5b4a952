"""Pallas TPU kernel: VL-BFGS Gram matrix (paper Alg. 1 line 6 via [44]).

Computes the (2m+1)x(2m+1) dot-product matrix of the L-BFGS basis
[s_0..s_{m-1}, y_0..y_{m-1}, g] in ONE blocked pass over the d-dimensional
vectors: grid over D blocks, each step loads an (n, D_BLK) tile once and
rank-updates the accumulator with tile @ tile.T on the MXU.  A naive
two-loop needs 4m separate O(d) passes (each dot re-reads its vectors from
HBM); this kernel reads each basis element exactly once — an (4m : 1) HBM
traffic reduction for the optimizer's hot step, which is why it exists.

The n dimension (21 for m=10) is zero-padded to the 8-sublane boundary by
Pallas automatically; the matmul runs n x D_BLK @ D_BLK x n.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

D_BLK = 4096


def _kernel(basis_ref, out_ref):
    d = pl.program_id(0)

    @pl.when(d == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile = basis_ref[...].astype(jnp.float32)      # (n, D_BLK)
    # full f32 products: at Mosaic's default precision the Gram of a
    # VGG11-wide basis came out 1.2e-3 (of its largest entry) off the
    # f32 oracle on a v5e; with n=21 rows the kernel is bound by its HBM
    # read, so the extra MXU passes cost little
    out_ref[...] += jax.lax.dot_general(
        tile, tile, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def gram(basis, interpret: bool = False):
    """basis: (n, D) -> (n, n) f32 Gram matrix."""
    n, D = basis.shape
    db = min(D_BLK, D)
    nd = pl.cdiv(D, db)
    padded = D
    if D % db:
        padded = nd * db
        basis = jnp.pad(basis, ((0, 0), (0, padded - D)))
    return pl.pallas_call(
        _kernel,
        grid=(nd,),
        in_specs=[pl.BlockSpec((n, db), lambda d: (0, d))],
        out_specs=pl.BlockSpec((n, n), lambda d: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
    )(basis)
