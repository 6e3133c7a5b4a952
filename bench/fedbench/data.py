"""Inputs made from the seed: class-template images of a dataset's shape.

Each class has a smooth template, a random 4x4 pattern per channel
upsampled bilinearly to the image size, and every image is its class's
template plus Gaussian noise.  This is the template-and-noise idea of the
program's own synthetic data, kept here so that the program cannot move
the traffic.  Class counts are exact (CIFAR-10: 5,000 of each class), so
a non-IID-l partition deals every client the same number of examples.

The images are made on the device in one jitted call and copied to the
host once: the program receives host arrays, as it would from a loader.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_from_seed(seed: int, stream: int = 0) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits: ``PRNGKey`` keeps only
    the low 32 bits of a larger seed, so the high word is folded in."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(stream)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def _upsample(small, h: int, w: int):
    """Bilinear upsample of (sh, sw, c) to (h, w, c), corners aligned."""
    sh, sw, _ = small.shape
    yi = jnp.linspace(0.0, sh - 1, h)
    xi = jnp.linspace(0.0, sw - 1, w)
    y0 = jnp.floor(yi).astype(jnp.int32)
    x0 = jnp.floor(xi).astype(jnp.int32)
    y1 = jnp.minimum(y0 + 1, sh - 1)
    x1 = jnp.minimum(x0 + 1, sw - 1)
    wy = (yi - y0)[:, None, None]
    wx = (xi - x0)[None, :, None]
    a = small[y0][:, x0]
    b = small[y0][:, x1]
    c = small[y1][:, x0]
    d = small[y1][:, x1]
    return (a * (1 - wy) * (1 - wx) + b * (1 - wy) * wx
            + c * wy * (1 - wx) + d * wy * wx)


@functools.partial(jax.jit, static_argnames=("shape", "n_classes",
                                             "per_class_train",
                                             "per_class_test"))
def _make(key, noise, *, shape, n_classes, per_class_train, per_class_test):
    h, w, c = shape
    k_tmpl, k_tr, k_te, k_ptr, k_pte = jax.random.split(key, 5)
    small = jax.random.normal(k_tmpl, (n_classes, 4, 4, c), jnp.float32)
    templates = jax.vmap(lambda s: _upsample(s, h, w))(small).reshape(
        n_classes, h * w * c)

    def split(k_noise, k_perm, per_class):
        y = jax.random.permutation(
            k_perm, jnp.repeat(jnp.arange(n_classes, dtype=jnp.int32),
                               per_class))
        # images flat, (N, h*w*c): a narrow channel axis last would be
        # padded out on the device and slow to copy back
        x = templates[y] + noise * jax.random.normal(
            k_noise, (n_classes * per_class, h * w * c), jnp.float32)
        return x, y

    return split(k_tr, k_ptr, per_class_train) + split(k_te, k_pte,
                                                       per_class_test)


def make_images(seed: int, shape, n_classes: int, n_train: int, n_test: int,
                noise: float):
    """-> (x_train, y_train, x_test, y_test) as host arrays; exactly
    ``n_train / n_classes`` training images of every class."""
    if n_train % n_classes or n_test % n_classes:
        raise ValueError(f"{n_train} training and {n_test} test images do "
                         f"not split evenly over {n_classes} classes")
    out = _make(key_from_seed(seed), jnp.float32(noise), shape=tuple(shape),
                n_classes=n_classes, per_class_train=n_train // n_classes,
                per_class_test=n_test // n_classes)
    x_tr, y_tr, x_te, y_te = (np.asarray(a) for a in out)
    return (x_tr.reshape(-1, *shape), y_tr, x_te.reshape(-1, *shape), y_te)
