"""The reduction from a profiler trace to the per-layer numbers."""
import math

import pytest

from fedbench import trace

MS = 1_000_000  # ns


def _summary():
    # window 0..100 ms on one device; ops overlap in places, one starts
    # before the window and one ends after it
    ops = [[
        ["fusion.1", "jit_client_grad_fim(1)", -5 * MS, 10 * MS, ""],
        ["fim_diag.3", "jit_client_grad_fim(1)", 10 * MS, 20 * MS,
         "%fim_diag.3 = f32[1,64]{1,0} custom-call(f32[8,64]{1,0} %p)"],
        ["fusion.2", "jit_client_grad_fim(1)", 20 * MS, 15 * MS, ""],
        ["gram.1", "jit_gram(2)", 60 * MS, 10 * MS,
         "%gram.1 = f32[21,21]{1,0} custom-call(f32[21,64]{1,0} %p)"],
        ["copy.4", "jit_gram(2)", 95 * MS, 10 * MS, ""],
    ]]
    modules = [[["jit_client_grad_fim(1)", 10 * MS, 25 * MS],
                ["jit_client_grad_fim(1)", 40 * MS, 5 * MS],
                ["jit_gram(2)", 60 * MS, 10 * MS]]]
    spans = [[trace.WINDOW, 0, 100 * MS],
             ["client_step", 0, 50 * MS],
             ["server_step", 55 * MS, 45 * MS]]
    return trace.Summary(window=(0, 100 * MS), ops=ops, modules=modules,
                         spans=spans)


def test_union_merges_and_clips():
    assert trace.union([(0, 10), (5, 10), (20, 5)], 0, 100) == [[0, 15],
                                                                 [20, 25]]
    assert trace.union([(-5, 10), (95, 10)], 0, 100) == [[0, 5], [95, 100]]


def test_busy_and_idle_share():
    s = _summary()
    # busy: [0,5) + [10,35) + [60,70) + [95,100) = 5 + 25 + 10 + 5 ms
    assert trace.busy_ns(s) == 45 * MS
    assert trace.idle_share(s) == pytest.approx(0.55)


def test_idle_share_without_device_ops_is_none():
    s = _summary()
    s.ops = []
    assert trace.idle_share(s) is None


def test_kernel_time_by_name():
    s = _summary()
    fim = trace.op_events(s, lambda o: o[0].startswith("fim_diag"))
    assert [o[3] for o in fim] == [20 * MS]
    # ops that cross the window's edge are left out
    assert len(trace.op_events(s, lambda o: o[1] == "jit_gram(2)")) == 1
    runs = trace.module_runs(s, lambda m: "client_grad_fim" in m)
    assert sum(d for _, d in runs) / len(runs) == 15 * MS


def test_roofline_names_the_binding_bound():
    # 1 GB at 1 GB/s is 1 s; 1 GFLOP at 1 TFLOP/s is 1 ms: memory binds
    share, bound = trace.roofline(1e9, 1e9, 2.0, 1e12, 1e9)
    assert (share, bound) == (pytest.approx(50.0), "memory")
    share, bound = trace.roofline(4e12, 1e6, 8.0, 1e12, 1e9)
    assert (share, bound) == (pytest.approx(50.0), "compute")


def test_top_ops_and_idle_gaps():
    s = _summary()
    top = trace.top_ops(s, n=2)
    assert top[0] == ["jit_client_grad_fim/fim_diag.3 f32[1,64]", 0.02]
    gaps = trace.idle_gaps(s, names=("client_step", "server_step"))
    # gaps: [5,10) client_step, [35,60) client_step/none at 47.5 ms,
    # [70,95) server_step
    assert [g[0] for g in gaps] == ["client_step", "server_step",
                                    "client_step"]
    assert gaps[0][1] == pytest.approx(0.025)
    assert math.isclose(sum(g[1] for g in gaps), 0.055)


def test_ops_take_their_names_and_programs():
    text = ("%fim_diag.35 = f32[1,2359296]{1,0:T(1,128)S(1)} custom-call("
            "f32[512,2359296]{1,0:T(8,128)} %pad.43)")
    assert trace.op_name(text) == "fim_diag.35"
    assert trace.result_type(text) == "f32[1,2359296]"
    assert trace.program("jit_gram(14779855184252225376)") == "jit_gram"
    ops = [["a", "", 5, 1, ""], ["b", "", 12, 1, ""], ["c", "", 30, 1, ""]]
    trace.assign_modules(ops, [["jit_f(1)", 0, 10], ["jit_g(2)", 10, 10]])
    assert [o[1] for o in ops] == ["jit_f(1)", "jit_g(2)", ""]


# ---------------------------------------------------------------------------
# a recorded trace: two client steps of vgg11-fimlbfgs on a v5e
# ---------------------------------------------------------------------------
def _recorded():
    from fedbench import manifest
    d = manifest.load_json(manifest.BENCH_DIR / "tests" / "data"
                           / "vgg11-two-client-steps.json")
    return trace.Summary.from_dict(d)


def _context(summary):
    from fedbench import harness, manifest
    cell = manifest.load_cell("vgg11-fimlbfgs")
    return harness.Context(cell=cell, setup_s=0.0,
                           window={"wall_s": 1.0, "rounds": 1, "times": [1.0]},
                          peaks=harness.load_peaks("TPU v5 lite"), chips=1,
                          trace=summary)


def test_recorded_busy_matches_a_brute_force_timeline():
    import numpy as np
    s = _recorded()
    lo, hi = s.window
    step = 1000  # 1 us
    timeline = np.zeros(int((hi - lo) // step) + 1, bool)
    for o in s.ops[0]:
        a = max(o[2], lo)
        b = min(o[2] + o[3], hi)
        if b > a:
            timeline[int((a - lo) // step):int(-(-(b - lo) // step))] = True
    brute = timeline.sum() * step
    assert trace.busy_ns(s) == pytest.approx(brute, rel=2e-3)
    # the host works between the two steps: the device waits then
    assert 0.05 < trace.idle_share(s) < 0.3


def test_recorded_ops_fall_in_their_programs():
    s = _recorded()
    progs = {trace.program(o[1]) for o in s.ops[0]}
    assert "jit_client_grad_fim" in progs
    fim = [o for o in s.ops[0] if o[0].startswith("fim_diag")]
    assert len(fim) == 40 and all(o[1].startswith("jit_client_grad_fim")
                                  for o in fim)


def test_recorded_readers():
    from fedbench import manifest
    s = _recorded()
    ctx = _context(s)
    metrics = {m.name: m for m in manifest.load_cell("vgg11-fimlbfgs").metrics}
    step_ms = metrics["client_step_ms"].reader().read(ctx)
    assert 250 < step_ms < 350
    share = metrics["fim_diag_roofline"].reader().read(ctx)
    assert 0 < share <= 100
    # the Gram kernel did not run between two client steps: nothing to read
    assert metrics["gram_roofline"].reader().read(ctx) is None
    idle = metrics["device_idle"].reader().read(ctx)
    assert idle == pytest.approx(100 * trace.idle_share(s))
