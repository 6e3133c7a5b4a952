"""Diagonal-Fisher estimation (paper Eq. 9 + diagonalization)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fim


def _quadratic_per_example(params, x, y):
    return 0.5 * jnp.sum((params["w"] * x - y) ** 2)


def test_per_example_diag_matches_manual():
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.normal(size=5))}
    xs = jnp.asarray(rng.normal(size=(16, 5)))
    ys = jnp.asarray(rng.normal(size=(16, 5)))
    diag = fim.per_example_diag(_quadratic_per_example, params, xs, ys)
    # manual: grad_i = (w*x_i - y_i) * x_i; diag = mean_i grad_i^2
    g = (np.asarray(params["w"]) * np.asarray(xs) - np.asarray(ys)) * np.asarray(xs)
    np.testing.assert_allclose(np.asarray(diag["w"]), (g ** 2).mean(0), rtol=1e-5)


def _conv_per_example(params, x, y):
    out = jax.lax.conv_general_dilated(
        x[None], params["w"], (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + params["b"]
    return 0.5 * jnp.sum((out[0] - y) ** 2)


@pytest.mark.parametrize("kernels", ["off", "on"])
def test_per_example_diag_conv_leaf(kernels):
    """A conv leaf goes to the Fisher op in its own (B, 3, 3, Cin, Cout)
    shape; the kernel ("on": interpret mode here) and the oracle both
    give the mean over the batch of each example's squared gradient."""
    rng = np.random.default_rng(1)
    params = {"w": jnp.asarray(rng.normal(size=(3, 3, 2, 8)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32)}
    xs = jnp.asarray(rng.normal(size=(11, 5, 5, 2)), jnp.float32)
    ys = jnp.asarray(rng.normal(size=(11, 5, 5, 8)), jnp.float32)
    diag = fim.per_example_diag(_conv_per_example, params, xs, ys,
                                kernels=kernels)
    per_ex = [jax.grad(_conv_per_example)(params, x, y)
              for x, y in zip(xs, ys)]
    for name in params:
        manual = np.mean([np.asarray(g[name]) ** 2 for g in per_ex], axis=0)
        assert diag[name].shape == params[name].shape
        np.testing.assert_allclose(np.asarray(diag[name]), manual,
                                   rtol=1e-5, atol=1e-6)


def test_microbatch_diag_is_squared_grad():
    g = {"a": jnp.asarray([-2.0, 3.0])}
    d = fim.microbatch_diag(g)
    np.testing.assert_allclose(np.asarray(d["a"]), [4.0, 9.0])


def test_microbatch_diag_kernel_keeps_leaf_shape():
    """The B=1 row of the fused op, through the kernel, on a 2-D leaf."""
    g = {"w": jnp.arange(12.0).reshape(3, 4) - 5.0}
    d = fim.microbatch_diag(g, kernels="on")
    np.testing.assert_allclose(np.asarray(d["w"]), np.asarray(g["w"]) ** 2)


def test_ema_update_and_warmup():
    params = {"a": jnp.zeros(3)}
    st = fim.init(params)
    d1 = {"a": jnp.asarray([1.0, 2.0, 3.0])}
    st = fim.update(st, d1, ema=0.9)
    np.testing.assert_allclose(np.asarray(st.diag["a"]), [1, 2, 3])  # warmup: copy
    d2 = {"a": jnp.asarray([2.0, 2.0, 2.0])}
    st = fim.update(st, d2, ema=0.5)
    np.testing.assert_allclose(np.asarray(st.diag["a"]), [1.5, 2.0, 2.5])


def test_smooth_y_lower_bound():
    """y = (Γ + λI)s must satisfy <s, y> >= λ'||s||² (Assumption 1 / Lemma 1)."""
    params = {"a": jnp.zeros(4)}
    st = fim.init(params)
    st = fim.update(st, {"a": jnp.asarray([0.0, 0.0, 1.0, 4.0])}, ema=0.9)
    s = {"a": jnp.asarray([1.0, -1.0, 2.0, 0.5])}
    lam_abs = 1e-3
    y = fim.smooth_y(st, s, damping=lam_abs, rel_damping=0.1)
    sy = float(jnp.vdot(s["a"], y["a"]))
    ss = float(jnp.vdot(s["a"], s["a"]))
    lam_eff = lam_abs + 0.1 * float(fim.mean_diag(st))
    assert sy >= lam_eff * ss - 1e-6
