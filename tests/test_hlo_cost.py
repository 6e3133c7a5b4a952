"""Regression tests for the trip-count-aware HLO cost analyzer — the
roofline's foundation.  XLA's own cost_analysis counts while bodies once;
these fixtures pin the corrected behaviour."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze, xla_cost_analysis


def _compile(f, *shapes):
    return jax.jit(f).lower(*shapes).compile().as_text()


def test_xla_cost_analysis_undercounts_scans():
    """Documents the bug we correct: XLA reports ONE body's flops."""
    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=10)
        return y

    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((256, 256), jnp.float32)).compile()
    xla_flops = xla_cost_analysis(comp).get("flops", 0)
    one_matmul = 2 * 256 ** 3
    assert xla_flops <= 1.5 * one_matmul  # ~1 matmul, not 10


@pytest.mark.parametrize("length", [1, 7, 10])
def test_flat_scan_flops(length):
    def f(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=length)
        return y

    txt = _compile(f, jax.ShapeDtypeStruct((256, 256), jnp.float32))
    r = analyze(txt)
    expect = length * 2 * 256 ** 3
    assert abs(r["flops"] - expect) / expect < 0.02


def test_nested_scan_flops():
    def body(c, _):
        return c @ c, None

    def f(x):
        def outer(c, _):
            d, _ = jax.lax.scan(body, c, None, length=5)
            return d, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    txt = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    r = analyze(txt)
    expect = 20 * 2 * 128 ** 3
    assert abs(r["flops"] - expect) / expect < 0.02


def test_collectives_weighted_by_trip_count():
    import subprocess, sys
    # needs >1 device: run in a subprocess with 4 host devices
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_cost import analyze
mesh = jax.make_mesh((4,), ("model",),
                     axis_types=(jax.sharding.AxisType.Auto,))
def f(x, w):
    def step(c, _):
        return jnp.einsum("bd,df->bf", c, w), None   # TP AR per iteration
    y, _ = jax.lax.scan(step, x, None, length=6)
    return y
x = jax.ShapeDtypeStruct((8, 256), jnp.float32)
w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
comp = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, None)),
                                NamedSharding(mesh, P("model", None)))).lower(x, w).compile()
r = analyze(comp.as_text())
per_ar = 8 * 256 * 4  # result bytes f32
assert r["collective_total"] >= 5 * per_ar, r  # ~6 iterations, not 1
print("COLL_TRIP_OK", r["collective_total"] / per_ar)
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=__file__.rsplit("/tests/", 1)[0],
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert "COLL_TRIP_OK" in proc.stdout, proc.stderr[-1500:]
