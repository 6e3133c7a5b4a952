"""Sharding rule unit tests + an 8-device host-platform integration test of
the dry-run machinery (subprocess: device count must not leak into this
process)."""
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.utils import sharding as shd


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_spec_divisible():
    mesh = FakeMesh({"data": 16, "model": 16})
    assert shd.spec_for((4096, 8192), "embed,mlp", mesh) == P(None, "model")
    assert shd.spec_for((49152, 4096), "vocab,embed", mesh) == P("model", None)


def test_spec_divisibility_fallback():
    """phi4's 24 heads don't divide 16 -> replicate that dim only."""
    mesh = FakeMesh({"data": 16, "model": 16})
    assert shd.spec_for((2, 24, 128), "layers,heads,head_dim", mesh) == P(None, None, None)
    assert shd.spec_for((2, 48, 128), "layers,heads,head_dim", mesh) == P(None, "model", None)


def test_missing_mesh_axis_dropped():
    mesh = FakeMesh({"data": 4, "model": 2})
    spec = shd.spec_for((8, 16), "batch,embed", mesh)  # batch maps (pod,data)
    assert spec == P("data", None)


def test_axis_not_reused():
    mesh = FakeMesh({"data": 2, "model": 2})
    spec = shd.spec_for((4, 4), "mlp,qkv", mesh)  # both map to model
    assert spec == P("model", None)


def test_opt_rules_shard_embed_over_data():
    mesh = FakeMesh({"data": 16, "model": 16})
    spec = shd.spec_for((10, 36, 4096, 14336), "history,layers,embed,mlp",
                        mesh, shd.OPT_RULES)
    assert spec == P(None, None, "data", "model")


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.launch.dryrun import build_step
from repro.configs.granite_8b import smoke_config
from repro.configs.base import ShapeConfig
from repro.models import model as zoo
from repro.utils import sharding as shd
from repro.models.layers import use_mesh

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = smoke_config().replace(dtype="float32")
shape = ShapeConfig("t", 64, 8, "train")
step, arg_shapes, arg_axes, donate = build_step(cfg, shape, "fim_lbfgs", 2)
in_sh = [shd.shardings_for_tree(s, a, mesh, shd.OPT_RULES if i == 1 else None)
         for i, (s, a) in enumerate(zip(arg_shapes, arg_axes))]
with use_mesh(mesh):
    compiled = jax.jit(step, in_shardings=tuple(in_sh)).lower(*arg_shapes).compile()
assert compiled.memory_analysis() is not None
# ALSO run it for real on the 8 fake devices: numerics must hold sharded
import numpy as np
from repro.launch import train as trainlib
ocfg = trainlib.opt_config(cfg)
params, axes, opt, opt_axes = trainlib.init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
batch = zoo.synth_batch(cfg, shape, jax.random.PRNGKey(1))
with use_mesh(mesh):
    p2, o2, stats = jax.jit(step, in_shardings=tuple(in_sh))(params, opt, batch)
assert np.isfinite(float(stats["loss"])), stats
print("MINI_DRYRUN_OK", float(stats["loss"]))
"""


@pytest.mark.slow
def test_mini_dryrun_on_8_host_devices():
    """End-to-end pjit of the federated train step on an 8-device host mesh:
    lowers, compiles AND executes with finite loss."""
    proc = subprocess.run(
        [sys.executable, "-c", MINI_DRYRUN],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"},
        cwd=__file__.rsplit("/tests/", 1)[0],
    )
    assert "MINI_DRYRUN_OK" in proc.stdout, proc.stderr[-2000:]
