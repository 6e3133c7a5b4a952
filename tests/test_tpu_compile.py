"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) accepts any block shape; the
TPU compiler does not.  These tests compile — without running — each
kernel for one chip of a described ``v5e:2x2`` topology at the sizes
full-width CIFAR VGG11 (9 488 266 parameters) gives it, and check that
the program really holds the kernel (``tpu_custom_call``); the Fisher
kernel's callers are also compiled whole, to check what XLA puts in front
of the kernel.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
from __future__ import annotations

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import FMNIST_CNN
from repro.core import fim
from repro.fed import client
from repro.kernels import codec_ops, fim_diag, ops, vlbfgs
from repro.models import cnn

VGG11_PARAMS = 9_488_266
CONV_512 = (3, 3, 512, 512)          # VGG11's largest leaf
PAYLOAD = 2 * VGG11_PARAMS           # fim_lbfgs upload: gradient + Fisher


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e chip, with the persistent compilation cache off: a program
    compiled for a described chip is written to it but cannot be read
    back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fim_diag(s):
    b, d = 100, math.prod(CONV_512)
    return jax.jit(fim_diag.fim_diag).lower(
        _spec(s, (b, d)), _spec(s, (d,)), _spec(s, ()))


def _vlbfgs_gram(s):
    return jax.jit(vlbfgs.gram).lower(_spec(s, (21, VGG11_PARAMS)))


def _int8_roundtrip(s):
    return jax.jit(codec_ops.int8_roundtrip).lower(
        _spec(s, CONV_512), _spec(s, CONV_512), _spec(s, ()))


def _topk_select(s):
    k = math.ceil(0.01 * PAYLOAD)
    return jax.jit(lambda flat: codec_ops.topk_select(flat, k)).lower(
        _spec(s, (PAYLOAD,)))


def _vmapped_over_cohort(s):
    """The vmapped cohort step batches the Fisher and codec kernels over
    clients; a bias leaf (64) keeps the unpadded, whole-array blocks, and
    a conv leaf comes in its own (B, 3, 3, Cin, Cout) shape."""
    def step(g, w, x, u, scale):
        diag = jax.vmap(fim_diag.fim_diag, in_axes=(0, None, None))(
            g, jnp.zeros((64,)), 0.0)
        wdiag = jax.vmap(fim_diag.fim_diag, in_axes=(0, None, None))(
            w, jnp.zeros((3, 3, 8, 128)), 0.0)
        q = jax.vmap(codec_ops.int8_roundtrip)(x, u, scale)
        top = jax.vmap(lambda f: codec_ops.topk_select(f, 7))(x)
        return diag, wdiag, q, top
    return jax.jit(step).lower(_spec(s, (4, 100, 64)),
                               _spec(s, (4, 100, 3, 3, 8, 128)),
                               _spec(s, (4, 64)), _spec(s, (4, 64)),
                               _spec(s, (4,)))


@pytest.mark.parametrize("lower", [_fim_diag, _vlbfgs_gram, _int8_roundtrip,
                                   _topk_select, _vmapped_over_cohort],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, lower):
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _fisher_of_conv_512(s):
    """``fim.per_example_diag``'s leaf path on VGG11's largest leaf, the
    per-example gradients as ``vmap(grad)`` leaves them: B=500, one leaf."""
    return 500, 1, jax.jit(lambda g: fim._leaf_diag(g, "auto")).lower(
        _spec(s, (500,) + CONV_512))


def _fmnist_client_step(s):
    """The whole client step at F-MNIST's bench widths (32, 64, 512) and
    client size (B=600): eight leaves, each its own Fisher kernel call."""
    cfg = dataclasses.replace(FMNIST_CNN, conv_channels=(32, 64),
                              fc_units=(512,))
    params = jax.eval_shape(lambda: cnn.init(cfg, jax.random.PRNGKey(0))[0])
    step = client.make_grad_fim_fn(
        lambda p, b: cnn.softmax_loss(p, cfg, b),
        cnn.per_example_loss_fn(cfg), "per_example", kernels="auto")
    batch = {"x": _spec(s, (600,) + tuple(cfg.input_shape)),
             "y": _spec(s, (600,), jnp.int32)}
    return 600, len(jax.tree.leaves(params)), step.lower(jax.tree.map(
        lambda p: _spec(s, p.shape, p.dtype), params), batch)


@pytest.mark.parametrize("lower", [_fisher_of_conv_512, _fmnist_client_step],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_fisher_reads_per_example_gradients_in_place(one_chip, monkeypatch,
                                                     lower):
    """The Fisher kernel takes each leaf's (B, *shape) per-example
    gradients as they are: one ``fim_diag`` custom call per leaf, no pad
    of the batch past B and no copy into (B, D) tiling, (B/8, 8, ...)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)  # the native kernel
    batch, leaves, lowered = lower(one_chip)
    text = lowered.compile().as_text()
    calls = re.findall(r"%fim_diag[.\d]* = .*custom_call_target=\"tpu_custom_call\"",
                       text)
    shapes = {op: [tuple(int(d) for d in dims.split(",") if d)
                   for dims in re.findall(rf"%{op}[.\d]* = \w+\[([\d,]*)\]",
                                          text)]
              for op in ("pad", "copy")}
    assert len(calls) == leaves
    assert not [sh for sh in shapes["pad"] if sh and sh[0] > batch]
    assert not [sh for sh in shapes["copy"]
                if sh[:2] == (-(-batch // 8), 8)]
