"""FLOP and parameter counts from the shapes alone."""
import jax
import jax.numpy as jnp
import pytest

from fedbench import harness, manifest


def _config(name):
    return manifest.load_json(manifest.BENCH_DIR / "configs" / f"{name}.json")


@pytest.mark.parametrize("name,params,flops", [
    # XLA's cost analysis of the forward pass counts 89,530,888 for VGG11:
    # the same taps plus bias adds, ReLU and pooling
    ("cifar10-vgg11", 9_488_266, 89_189_888),
    ("fmnist-cnn", 1_630_090, 10_205_440),
])
def test_counts(name, params, flops):
    cfg = _config(name)
    fam = manifest.load_module(manifest.BENCH_DIR / "families" / "cnn.py")
    assert fam.n_params(cfg) == params == cfg["n_params"]
    assert sum(fam.leaf_sizes(cfg)) == params
    assert fam.forward_flops(cfg) == flops


@pytest.mark.parametrize("name", ["cifar10-vgg11", "fmnist-cnn"])
def test_flops_against_xla(name):
    """XLA's count of the same forward pass lies above the counter by
    the elementwise work only (under 3%)."""
    cfg = _config(name)
    fam = manifest.load_module(manifest.BENCH_DIR / "families" / "cnn.py")
    params = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    x = jax.ShapeDtypeStruct((1, *cfg["input_shape"]), jnp.float32)
    cost = jax.jit(lambda p, x: fam.forward(p, cfg, x)).lower(
        params, x).compile().cost_analysis()
    xla = cost["flops"] if isinstance(cost, dict) else cost[0]["flops"]
    assert 1.0 <= xla / fam.forward_flops(cfg) < 1.03


def test_program_lays_out_the_weights_as_the_family_does():
    from repro.configs.paper_models import CIFAR_VGG
    from repro.models import cnn
    cfg = _config("cifar10-vgg11")
    fam = manifest.load_module(manifest.BENCH_DIR / "families" / "cnn.py")
    mine = jax.eval_shape(lambda: fam.init(cfg, jax.random.PRNGKey(0)))
    theirs = jax.eval_shape(lambda: cnn.init(CIFAR_VGG,
                                             jax.random.PRNGKey(0))[0])
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), theirs)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.load_peaks("TPU v99 imaginary")
    assert harness.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
