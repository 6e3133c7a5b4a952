"""The CNN family: the paper's classifiers (arXiv:2110.07567 Sec. VI-A).

What the benchmark needs of a model family, kept apart from the program:

* ``init``: the weights, made on the device in one jitted call from the
  seed, in the layout the program's CNN takes;
* ``client_stats``: the plain float32 reference of one client's work,
  its loss, gradient and exact per-example Fisher diagonal (Eq. 9),
  in blocks of examples so that it fits beside nothing else;
* ``forward_flops`` and ``n_params``: counts from the shapes alone;
* ``program_config``: the program's own configuration object, the one
  place this file touches the program.

The reference is written from the architecture: 3x3 convolutions with
SAME padding, ReLU, 2x2 max-pooling with SAME padding after every
convolution, dense layers with ReLU, and a linear head, with the loss the
mean softmax cross-entropy.  It imports nothing of the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _layers(cfg: dict):
    """[(name, weight shape)] in the program's layout."""
    if list(cfg["kernel_size"]) != [3, 3]:
        raise ValueError(f"kernel_size {cfg['kernel_size']}: the program's "
                         "CNN has 3x3 convolutions only")
    h, w, c = cfg["input_shape"]
    ph, pw = cfg["pool"]
    out = []
    for i, ch in enumerate(cfg["conv_channels"]):
        out.append((f"conv{i}", (3, 3, c, ch)))
        c = ch
        h, w = -(-h // ph), -(-w // pw)
    feat = h * w * c
    for j, units in enumerate(cfg["fc_units"]):
        out.append((f"fc{j}", (feat, units)))
        feat = units
    out.append(("out", (feat, cfg["num_classes"])))
    return out


def n_params(cfg: dict) -> int:
    return sum(math.prod(s) + s[-1] for _, s in _layers(cfg))


def leaf_sizes(cfg: dict) -> list:
    """Element counts of every weight and bias, one entry per leaf."""
    return [n for _, s in _layers(cfg) for n in (math.prod(s), s[-1])]


def forward_flops(cfg: dict) -> int:
    """Multiply-adds of one example's forward pass, two FLOPs each.

    A 3x3 SAME convolution counts only the taps that land inside the
    input: (3h-2)(3w-2) of them for h, w >= 2, one for a 1-wide axis.
    Bias adds, ReLU and pooling are left out; they are not MXU work."""
    h, w, c = cfg["input_shape"]
    ph, pw = cfg["pool"]
    flops = 0
    for ch in cfg["conv_channels"]:
        taps = (3 * h - 2 if h > 1 else 1) * (3 * w - 2 if w > 1 else 1)
        flops += 2 * c * ch * taps
        c = ch
        h, w = -(-h // ph), -(-w // pw)
    feat = h * w * c
    for units in list(cfg["fc_units"]) + [cfg["num_classes"]]:
        flops += 2 * feat * units
        feat = units
    return flops


@functools.partial(jax.jit, static_argnames=("layers",))
def _init(key, layers):
    keys = jax.random.split(key, len(layers))
    params = {}
    for k, (name, shape) in zip(keys, layers, strict=True):
        fan_in = shape[-2]
        w = jax.random.normal(k, shape, jnp.float32) / math.sqrt(fan_in)
        if name.startswith("conv"):
            w = w / 3.0
        params[name] = {"w": w, "b": jnp.zeros((shape[-1],), jnp.float32)}
    return params


def init(cfg: dict, key) -> dict:
    """He-style normal weights (convolutions scaled by 1/3), zero biases."""
    return _init(key, tuple((n, tuple(s)) for n, s in _layers(cfg)))


def program_config(cfg: dict):
    """The program's ``CNNConfig`` for this file."""
    from repro.configs.paper_models import CNNConfig
    return CNNConfig(name=cfg["name"], input_shape=tuple(cfg["input_shape"]),
                     num_classes=cfg["num_classes"],
                     conv_channels=tuple(cfg["conv_channels"]),
                     fc_units=tuple(cfg["fc_units"]),
                     pool=tuple(cfg["pool"]), dataset=cfg["dataset"])


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
FP8 = jnp.float8_e4m3fn


def _fp8(x):
    """Round to float8 (e4m3) under a per-tensor scale, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(FP8).max)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_in(x):
    """float8 on the way in; the cotangent passes as it is."""
    return _fp8(x)


_fp8_in.defvjp(lambda x: (_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_back(x):
    """Identity on the way in; the cotangent rounded to float8."""
    return x


_fp8_back.defvjp(lambda x: (x, None), lambda _, g: (_fp8(g),))


def forward(params, cfg: dict, x, dtype=jnp.float32):
    """Logits of a batch (B, H, W, C), computed in ``dtype``.

    float32 runs every product at HIGHEST precision: a TPU would
    otherwise multiply float32 in one bfloat16 pass.  bfloat16 keeps
    weights, activations and gradients in bfloat16.  float8 (the e4m3
    format) rounds both operands of every product to float8 under a
    per-tensor scale, forward and backward, and keeps the rest in
    float32, as float8 training does."""
    dtype = jnp.dtype(dtype)
    fp8 = dtype == FP8
    store = jnp.float32 if fp8 else dtype
    prec = HIGHEST if store == jnp.float32 else None

    def mm(fn, a, w):
        if fp8:
            return _fp8_back(fn(_fp8_in(a), _fp8_in(w)))
        return fn(a, w.astype(store))

    conv = functools.partial(jax.lax.conv_general_dilated,
                             window_strides=(1, 1), padding="SAME",
                             dimension_numbers=("NHWC", "HWIO", "NHWC"),
                             precision=prec)
    dot = functools.partial(jnp.dot, precision=prec)
    ph, pw = cfg["pool"]
    x = x.astype(store)
    for i in range(len(cfg["conv_channels"])):
        p = params[f"conv{i}"]
        x = jax.nn.relu(mm(conv, x, p["w"]) + p["b"].astype(store))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                  (1, ph, pw, 1), (1, ph, pw, 1), "SAME")
    x = x.reshape(x.shape[0], -1)
    for j in range(len(cfg["fc_units"])):
        p = params[f"fc{j}"]
        x = jax.nn.relu(mm(dot, x, p["w"]) + p["b"].astype(store))
    p = params["out"]
    return mm(dot, x, p["w"]) + p["b"].astype(store)


def _example_loss(params, cfg, x, y, dtype):
    logits = forward(params, cfg, x[None], dtype)[0].astype(jnp.float32)
    return jax.nn.logsumexp(logits) - logits[y]


@functools.partial(jax.jit, static_argnames=("cfg_key", "block", "dtype"))
def _client_stats(params, x, y, *, cfg_key, block, dtype):
    cfg = dict(cfg_key)
    dtype = jnp.dtype(dtype)
    n = x.shape[0]
    grad_fn = jax.vmap(jax.value_and_grad(_example_loss),
                       in_axes=(None, None, 0, 0, None))

    def body(acc, xy):
        xb, yb = xy
        lb, gb = grad_fn(params, cfg, xb, yb, dtype)
        loss, g1, g2 = acc
        g1 = jax.tree.map(lambda a, g: a + jnp.sum(g.astype(jnp.float32), 0),
                          g1, gb)
        g2 = jax.tree.map(
            lambda a, g: a + jnp.sum(jnp.square(g.astype(jnp.float32)), 0),
            g2, gb)
        return (loss + jnp.sum(lb.astype(jnp.float32)), g1, g2), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    xs = x.reshape((n // block, block) + x.shape[1:])
    ys = y.reshape((n // block, block))
    (loss, g1, g2), _ = jax.lax.scan(body, (jnp.float32(0.0), zeros, zeros),
                                     (xs, ys))
    return (loss / n, jax.tree.map(lambda a: a / n, g1),
            jax.tree.map(lambda a: a / n, g2))


def _freeze(cfg: dict):
    return tuple((k, tuple(v) if isinstance(v, list) else v)
                 for k, v in sorted(cfg.items())
                 if k in ("input_shape", "pool", "conv_channels", "fc_units",
                          "kernel_size", "num_classes"))


def client_stats(params, cfg: dict, x, y, dtype=jnp.float32, block=None):
    """One client's (mean loss, mean gradient, mean squared per-example
    gradient), each gradient a tree like ``params`` in float32.  The
    batch gradient is the mean of the per-example gradients, which is the
    gradient of the mean loss."""
    n = len(y)
    block = block or _block(n)
    return _client_stats(params, jnp.asarray(x), jnp.asarray(y),
                         cfg_key=_freeze(cfg), block=block,
                         dtype=jnp.dtype(dtype).name)


def _block(n: int, cap: int = 128) -> int:
    """The largest divisor of n that is at most ``cap``."""
    return max(d for d in range(1, min(n, cap) + 1) if n % d == 0)
