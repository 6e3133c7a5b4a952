"""The readers of the program's own host spans and counters
(``fedbench/program_spans.py``, ``bench/metrics/``): on a synthetic
summary, on a CPU run of the tiny cell, and on a recorded chip run of
``fmnist-fimlbfgs`` (``data/fmnist-host-spans.json``, written by
``bench/host_spans.py``)."""
import time

import jax
import pytest

import tiny
from fedbench import harness, manifest, program_spans, trace

MS = 1_000_000  # ns
READERS = ("client_host_ms", "server_step_ms", "aggregate_ms",
           "evaluate_ms", "h2d_mb_per_round", "lowered_per_round")


def _reader(name):
    return manifest.load_module(manifest.BENCH_DIR / "metrics"
                                / f"{name}.py")


def _summary():
    # one round in a 100-ms window: two clients (their loss reads 10 and
    # 5 ms), aggregation, the server step, an evaluation; the device idles
    # in client 2's copy (30-34 ms) and between rounds (97-100 ms)
    spans = [[trace.WINDOW, 0, 100 * MS],
             ["fed.round", 0, 80 * MS],
             ["fed.sample", 0, 2 * MS],
             ["fed.gather", 2 * MS, 8 * MS],
             ["fed.client", 10 * MS, 20 * MS],
             ["client_step", 10 * MS, 20 * MS],
             ["fed.put", 10 * MS, 4 * MS],
             ["fed.dispatch", 14 * MS, 1 * MS],
             ["fed.wait", 15 * MS, 10 * MS],
             ["fed.client", 30 * MS, 30 * MS],
             ["client_step", 30 * MS, 30 * MS],
             ["fed.put", 30 * MS, 6 * MS],
             ["fed.dispatch", 36 * MS, 2 * MS],
             ["fed.wait", 38 * MS, 5 * MS],
             ["fed.aggregate", 60 * MS, 4 * MS],
             ["fed.server_step", 64 * MS, 16 * MS],
             ["server_step", 64 * MS, 16 * MS],
             ["fed.evaluate", 80 * MS, 17 * MS],
             # outside the window: left out
             ["fed.server_step", 150 * MS, 50 * MS]]
    ops = [[["a", "", 0, 30 * MS, ""], ["b", "", 34 * MS, 63 * MS, ""]]]
    return trace.Summary(window=(0, 100 * MS), ops=ops, modules=[[]],
                         spans=spans)


def _context(summary, counters=None, rounds=None):
    return program_spans.Context(
        cell=tiny.cell(), setup_s=0.0,
        window={"wall_s": 1.0, "rounds": 1, "times": [1.0]}, peaks=None,
        chips=1, trace=summary, counters=counters, traced_rounds=rounds)


def _counters(**totals):
    """A registry's ``as_dict`` with each total split over two spans."""
    return {name: {"kind": "counter",
                   "points": [[{"span": "fed.client"}, v - 1],
                              [{"span": ""}, 1]]}
            for name, v in totals.items()}


def test_span_readers_on_a_synthetic_round():
    ctx = _context(_summary())
    got = {m: _reader(m).read(ctx) for m in READERS[:4]}
    # each client's span less its own wait: (20 - 10 + 30 - 5) / 2
    assert got == {"client_host_ms": pytest.approx(17.5),
                   "server_step_ms": pytest.approx(16.0),
                   "aggregate_ms": pytest.approx(4.0),
                   "evaluate_ms": pytest.approx(17.0)}


def test_counter_readers_per_round():
    ctx = _context(_summary(), _counters(h2d_bytes_total=3e8,
                                         jax_lowered_total=6), rounds=3)
    assert _reader("h2d_mb_per_round").read(ctx) == pytest.approx(100.0)
    assert _reader("lowered_per_round").read(ctx) == pytest.approx(2.0)
    # a counter that never moved reads 0
    ctx.counters = _counters(h2d_bytes_total=3e8)
    assert _reader("lowered_per_round").read(ctx) == 0.0


@pytest.mark.parametrize("ctx", [
    # the harness's own context: no counters, no program spans
    harness.Context(cell=tiny.cell(), setup_s=0.0,
                    window={"wall_s": 1.0, "rounds": 1, "times": [1.0]},
                    peaks=None, chips=1,
                    trace=trace.Summary(window=(0, 100 * MS),
                                        spans=[[trace.WINDOW, 0, 100 * MS]])),
    harness.Context(cell=tiny.cell(), setup_s=0.0,
                    window={"wall_s": 1.0, "rounds": 1, "times": [1.0]},
                    peaks=None, chips=1, trace=None),
], ids=["trace_without_program_spans", "untraced"])
def test_readers_find_nothing_where_the_harness_keeps_nothing(ctx):
    for m in READERS:
        assert _reader(m).read(ctx) is None


def test_gaps_take_the_innermost_program_span():
    s = _summary()
    names = harness.SPANS + program_spans.FED_SPANS
    gaps = trace.idle_gaps(s, names=names)
    # 30-34 ms: client 2's copy, inside client_step and fed.client;
    # 97-100 ms: between rounds, under no span of the program
    assert gaps == [["fed.put", pytest.approx(0.004)],
                    ["round_loop", pytest.approx(0.003)]]
    # the harness's names alone put the first on the client step
    assert trace.idle_gaps(s, names=harness.SPANS)[0][0] == "client_step"


# ---------------------------------------------------------------------------
# the tiny cell on the CPU, through host_spans.measure
# ---------------------------------------------------------------------------
def test_tiny_cell_through_host_spans(monkeypatch, tmp_path):
    import host_spans
    cell = tiny.cell()
    monkeypatch.setattr(harness, "check_device",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(harness, "load_peaks", lambda kind: None)
    monkeypatch.setattr(manifest, "load_cell", lambda name: cell)
    t0 = time.perf_counter()
    out = host_spans.measure("tiny-cell", seed=2**31 + 5, out=tmp_path)
    assert time.perf_counter() - t0 < 120
    m = out["metrics"]
    for name in READERS[:3]:
        assert m[name] > 0
    t = cell.traffic
    clients = int(t["participation"] * t["num_clients"])
    examples = cell.workload["client_examples"]
    per_client = examples * (8 * 8 * 1 * 4 + 4)     # float32 images, int32
    points = dict((lbl["span"], v) for lbl, v in
                  out["counters"]["h2d_bytes_total"])
    assert points["fed.client"] == t["trace_rounds"] * clients * per_client
    # the program's spans go away with the tracer: the benchmark's traced
    # rounds as they are read nothing of them
    assert all(out["metrics_plain"][name] is None for name in READERS)
    assert "fed.round" not in [g[0] for g in out["idle_gaps_plain"]]
    rec = manifest.load_json(tmp_path / "tiny-cell-summary.json")
    assert rec["traced_rounds"] == t["trace_rounds"]
    assert {s[0] for s in rec["spans"]} >= {"fed.round", "fed.client",
                                            "fed.put", "fed.server_step"}


# ---------------------------------------------------------------------------
# a recorded trace: 5 rounds of fmnist-fimlbfgs on a v5e, program spans on
# ---------------------------------------------------------------------------
def test_recorded_fmnist_rounds():
    d = manifest.load_json(manifest.BENCH_DIR / "tests" / "data"
                           / "fmnist-host-spans.json")
    s = trace.Summary.from_dict(d)
    ctx = _context(s, d["counters"], d["traced_rounds"])
    got = {m: _reader(m).read(ctx) for m in READERS}
    # 20 clients x 600 x (784 float32 pixels + an int32 label) a round, and
    # one evaluation of 2,000 such images in the 5 rounds
    assert got["h2d_mb_per_round"] == pytest.approx(
        20 * 600 * 3140 / 1e6 + 2000 * 3140 / 5 / 1e6, abs=1e-9)
    # the eager server step's two fori_loops, lowered anew each round
    lowered = d["counters"]["jax_lowered_total"]["points"]
    assert got["lowered_per_round"] == 2.0
    assert {lbl["span"] for lbl, _ in lowered} == {"fed.server_step"}
    # measured on one v5e chip (PERF.md): host time of a client outside
    # its loss read, of the server step, of aggregation, of an evaluation
    assert 1 < got["client_host_ms"] < 10
    assert 250 < got["server_step_ms"] < 350
    assert 100 < got["aggregate_ms"] < 160
    assert 1 < got["evaluate_ms"] < 10
    gaps = trace.idle_gaps(s, names=harness.SPANS + program_spans.FED_SPANS)
    names = {g[0] for g in gaps}
    assert not names & {"round_loop", "fed.round"}
    # the gather of the round's client data leaves the device idle longest
    assert gaps[0][0] == "fed.gather"
    assert names <= {"fed.gather", "server_step", "fed.put", "fed.wait"}
