"""Jit'd dispatch wrappers for the Pallas kernels.

On TPU the Pallas kernels run natively; elsewhere (this CPU container, and
any platform without Mosaic) they execute in interpret mode when explicitly
requested, otherwise fall back to the pure-jnp oracle in ref.py — identical
semantics either way (tests sweep shapes/dtypes asserting allclose; the
codec ops assert bit-exact equality).

Every wrapper takes a ``mode`` knob (``FedConfig.kernels`` surfaces it to
federated runs):

  * ``"auto"`` — native Pallas on TPU, jnp oracle elsewhere (default:
    zero behavior change on CPU, fast path where Mosaic exists);
  * ``"on"``   — native on TPU, *interpret-mode kernel* elsewhere (the
    CI/testing setting: exercises the kernel code path everywhere);
  * ``"off"``  — always the jnp oracle, even on TPU.

``force_kernel=True`` (the pre-knob API) is kept as an alias for "on".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import codec_ops as _codec
from repro.kernels import fim_diag as _fim
from repro.kernels import flash_attention as _fa
from repro.kernels import ref
from repro.kernels import vlbfgs as _vl

MODES = ("auto", "on", "off")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve(mode: str, force_kernel: bool = False) -> str:
    """-> "native" | "interpret" | "oracle" for the current backend."""
    if mode not in MODES:
        raise ValueError(f"kernels mode must be one of {MODES}, got {mode!r}")
    if force_kernel:
        mode = "on"
    if mode == "off":
        return "oracle"
    if _on_tpu():
        return "native"
    return "interpret" if mode == "on" else "oracle"


def fim_diag_update(grads, old_diag, ema, force_kernel: bool = False,
                    mode: str = "auto"):
    """Fused Γ update: ema*old + (1-ema)*mean_b g².  grads: (B, *shape),
    one leaf's per-example gradients in the leaf's own shape (a (B, D)
    matrix is the shape (D,)); old_diag: shape."""
    path = resolve(mode, force_kernel)
    if path == "oracle":
        return ref.fim_diag_ref(grads, old_diag, ema)
    return _fim.fim_diag(grads, old_diag, ema,
                         interpret=(path == "interpret"))


def vlbfgs_gram(basis, force_kernel: bool = False, mode: str = "auto"):
    """(2m+1, D) basis -> (2m+1, 2m+1) Gram matrix."""
    path = resolve(mode, force_kernel)
    if path == "oracle":
        return ref.vlbfgs_gram_ref(basis)
    return _vl.gram(basis, interpret=(path == "interpret"))


def int8_roundtrip(x, key, force_kernel: bool = False, mode: str = "auto"):
    """Fused int8 stochastic-rounding quantize+dequantize of one payload
    tensor.  Draws the rounding uniforms from ``key`` with the same
    ``jax.random.uniform(key, x.shape)`` stream on every path, so kernel
    and oracle round identically (bit-for-bit)."""
    size = x.size
    if size == 0:
        return x.astype(jnp.float32)
    u = jax.random.uniform(key, x.shape)
    scale = ref.int8_scale(x)  # shared: both paths quantize identically
    path = resolve(mode, force_kernel)
    if path == "oracle":
        return ref.int8_roundtrip_ref(x, u, scale)
    return _codec.int8_roundtrip(x, u, scale,
                                 interpret=(path == "interpret"))


def topk_select(flat, k, force_kernel: bool = False, mode: str = "auto"):
    """Zero all but the ``k`` largest-|x| entries of a 1-D payload via
    bucketed threshold select (no global sort; exactly ``k`` survive —
    the codec ``wire_bytes`` billing invariant)."""
    path = resolve(mode, force_kernel)
    if path == "oracle":
        return ref.topk_select_ref(flat, k)
    return _codec.topk_select(flat, k, interpret=(path == "interpret"))


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    force_kernel: bool = False, mode: str = "auto"):
    """(B,H,S,hd) x (B,KV,S,hd) -> (B,H,S,hd)."""
    path = resolve(mode, force_kernel)
    if path == "oracle":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               interpret=(path == "interpret"))
