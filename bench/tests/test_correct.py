"""``correct`` comes out false when the timed path is broken, and the
float8 control fails the real cells' limits."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from fedbench import compare, harness

SEED = 2**31 + 11


@pytest.fixture
def off_chip(monkeypatch):
    """The harness's look for a chip skipped: the run takes the CPU."""
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "load_peaks", lambda kind: None)
    return monkeypatch


def _run(monkeypatch, fault=None):
    """A whole run of the tiny cell; ``fault(run, monkeypatch)`` breaks
    the timed path once the program is built."""
    if fault is not None:
        build = harness.build

        def broken(cell, seed):
            b = build(cell, seed)
            fault(b.run, monkeypatch)
            return b

        monkeypatch.setattr(harness, "build", broken)
    return harness.run_cell(tiny.cell(), seed=SEED, seconds=0.5,
                            traced=False, t_start=time.perf_counter())


def _still(run, monkeypatch):
    """A step that returns its state unchanged."""
    monkeypatch.setattr(run.strategy, "server_step", lambda aggregate: None)


def _half_batch(run, monkeypatch):
    """Half of each client's batch left out, the mean over the rest."""
    data = run._client_data

    def half(k):
        x, y = data(k)
        return x[:len(y) // 2], y[:len(y) // 2]

    monkeypatch.setattr(run, "_client_data", half)


def _fisher_x2(run, monkeypatch):
    """Each client's Fisher diagonal doubled where it is produced."""
    step = run.strategy.client_step

    def doubled(*a, **k):
        (g, f), loss = step(*a, **k)
        return (g, jax.tree.map(lambda v: 2.0 * v, f)), loss

    monkeypatch.setattr(run.strategy, "client_step", doubled)


def _stale_history(run, monkeypatch):
    """The history stops taking pairs once it holds m: the ring that the
    window's rounds wrap never wraps."""
    from repro.core import lbfgs
    push = lbfgs.push

    def stale(h, s, y):
        m = jax.tree.leaves(h.s)[0].shape[0]
        new = push(h, s, y)
        return jax.tree.map(lambda a, b: jnp.where(h.count >= m, a, b),
                            h, new)

    monkeypatch.setattr(lbfgs, "push", stale)


def test_sound_run_is_correct(off_chip):
    result = _run(off_chip)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert {"round_s", "setup_s"} <= set(result["metrics"])


def test_reference_follows_rounds_of_the_window(off_chip, capsys):
    """The followed rounds reach past the warm-up into the window, and
    past m + 1, where the history's ring wraps."""
    cell = tiny.cell()
    follow = cell.workload["reference_rounds"]
    assert follow >= cell.traffic["lbfgs_m"] + 2
    _run(off_chip)
    assert f"reference_rounds={follow} " in capsys.readouterr().out


@pytest.mark.parametrize("fault", [_still, _half_batch, _fisher_x2,
                                   _stale_history])
def test_broken_timed_path_is_not_correct(off_chip, fault):
    result = _run(off_chip, fault)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", ["vgg11-fimlbfgs", "fmnist-fimlbfgs"])
def test_float8_control_fails_the_cells_limits(cell):
    """The reference with its products in float8, in the program's place,
    at a size a test can hold, against each real cell's limits; the
    program itself passes them."""
    c = tiny.cell(limits_from=cell)
    b = harness.build(c, seed=12345)
    follow = c.workload["reference_rounds"]
    rec = harness.Recorder(b.run, follow)
    with rec.recording():
        losses = harness.warmup(b.run, c.traffic)
        losses += harness.rounds(b.run, harness.WARMUP_ROUNDS + 1,
                                 follow - harness.WARMUP_ROUNDS, c.traffic)
    cohorts = harness.cohort_data(rec.cohorts, b.run.partition,
                                  b.x_train, b.y_train)
    params0 = jax.tree.map(np.asarray, b.params0)
    ref = harness.reference(c, params0, cohorts)
    low = harness.reference(c, params0, cohorts, dtype="float8_e4m3fn")
    correct, rows = compare.judge(compare.readings(low, ref, params0),
                                  c.workload["limits"])
    assert not correct, rows
    correct, rows = compare.judge(
        compare.readings(rec.readings(losses), ref, params0),
        c.workload["limits"])
    assert correct, rows
