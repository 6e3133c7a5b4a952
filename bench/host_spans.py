#!/usr/bin/env python3
"""The round's host steps on the chip: the program's own spans and
counters, read beside the device trace.  Needs the chip; the benchmark's
own runs never run this.

    python3 bench/host_spans.py --workload <cell> [<cell> ...] --seed <n> \
        [--out chiprun_out/host_spans]

For each cell, in one process, it builds and warms up the cell as a run
does, then drives ``trace_rounds`` rounds four times:

* ``plain``: no tracer, no profiler (as the measured window);
* ``spans``: ``repro.obs.Tracer(wall=True)`` attached, no profiler: each
  ``fed.*`` block's host duration from the tracer's memory;
* ``traced``: the harness's traced rounds with the tracer attached: the
  program's spans in the device trace beside the harness's, the
  per-layer readers of ``bench/metrics/`` on them (``client_host_ms``,
  ``server_step_ms``, ``aggregate_ms``, ``evaluate_ms``,
  ``h2d_mb_per_round``, ``lowered_per_round``, and the accepted ones),
  the idle gaps named by the innermost span of either; each ``fed.*``
  block's host duration again, now under the profiler;
* ``traced_plain``: the harness's traced rounds as the benchmark runs
  them, no tracer attached.

One JSON line per cell goes to standard output, and the ``traced``
summary (device ops merged into busy intervals) with the tracer's
counters to ``<out>/<cell>-summary.json``: ``fmnist-fimlbfgs``'s is
``bench/tests/data/fmnist-host-spans.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

import run as bench_run

READERS = ("client_host_ms", "server_step_ms", "aggregate_ms",
           "evaluate_ms", "h2d_mb_per_round", "lowered_per_round",
           "device_idle", "client_step_ms")


def _timed(harness, run, first: int, n: int, traffic: dict) -> list:
    """Host seconds of ``n`` rounds through the window's own calls."""
    times = []
    for t in range(first, first + n):
        r0 = time.perf_counter()
        harness._one_round(run, t, traffic["eval_every"],
                           traffic["eval_examples"])
        times.append(time.perf_counter() - r0)
    return times


def _span_stats(durations: dict) -> dict:
    """name -> {count, mean_ms, total_ms}."""
    return {name: {"count": len(ds), "mean_ms": sum(ds) / len(ds) * 1e3,
                   "total_ms": sum(ds) * 1e3}
            for name, ds in sorted(durations.items())}


def _by_name(pairs) -> dict:
    out: dict = {}
    for name, seconds in pairs:
        out.setdefault(name, []).append(seconds)
    return out


def recorded(summary, counters: dict, rounds: int,
             min_gap_ns: float = 1e6) -> dict:
    """The traced summary as a test fixture: the first device's ops
    merged into busy intervals, across idle gaps shorter than
    ``min_gap_ns`` too (the gaps the breakdown names are tens of ms),
    every kept host span, and the tracer's counters."""
    from fedbench import trace
    lo, hi = summary.window
    busy = (trace.union([(o[2], o[3]) for o in summary.ops[0]], lo, hi)
            if summary.ops else [])
    merged: list = []
    for s, e in busy:
        if merged and s - merged[-1][1] < min_gap_ns:
            merged[-1][1] = e
        else:
            merged.append([s, e])
    return {"source": "bench/host_spans.py on a v5e: traced rounds with "
                      "the program's spans; device ops merged into busy "
                      f"intervals across gaps under {min_gap_ns / 1e6:g} ms",
            "window": list(summary.window),
            "ops": [[["busy", "", s, e - s, ""] for s, e in merged]],
            "modules": [], "spans": summary.spans,
            "counters": counters, "traced_rounds": rounds}


def _read(ctx) -> dict:
    from fedbench import manifest
    return {m: manifest.load_module(
        manifest.BENCH_DIR / "metrics" / f"{m}.py").read(ctx)
        for m in READERS}


def measure(name: str, seed: int, out: Path) -> dict:
    from repro import obs

    from fedbench import harness, manifest, program_spans, trace

    cell = manifest.load_cell(name)
    devices = harness.check_device(cell.chips)
    peaks = harness.load_peaks(devices[0].device_kind)
    t = cell.traffic
    n = t["trace_rounds"]
    b = harness.build(cell, seed)
    run = b.run
    harness.warmup(run, t)
    first = harness.WARMUP_ROUNDS + 1

    plain = _timed(harness, run, first, n, t)
    first += n

    tracer = obs.Tracer(wall=True)
    run.tracer = tracer
    with_spans = _timed(harness, run, first, n, t)
    run.tracer = None
    first += n
    spans_host = _span_stats(_by_name(
        (s.name, s.dur) for s in tracer.spans if s.cat == obs.CAT_WALL))

    tracer = obs.Tracer(wall=True)
    with tempfile.TemporaryDirectory() as logdir:
        summary = program_spans.traced_rounds(run, tracer, n, first, t,
                                              logdir)
    first += n
    traced_host = _span_stats(_by_name(
        (s.name, s.dur) for s in tracer.spans if s.cat == obs.CAT_WALL))

    with tempfile.TemporaryDirectory() as logdir:
        summary_plain = harness.traced_rounds(run, n, first, t, logdir)

    # the wire-bytes mirror of the ledger is no host step's: left out
    counters = {k: v for k, v in tracer.metrics.as_dict().items()
                if k != "bytes_wire_total"}
    window = {"wall_s": sum(plain), "rounds": n, "times": plain}
    ctx = program_spans.Context(
        cell=cell, setup_s=0.0, window=window, peaks=peaks,
        chips=len(devices), trace=summary, counters=counters,
        traced_rounds=n)
    ctx_plain = program_spans.Context(
        cell=cell, setup_s=0.0, window=window, peaks=peaks,
        chips=len(devices), trace=summary_plain)
    result = {
        "cell": name, "seed": seed, "device": devices[0].device_kind,
        "round_s": {"plain": plain, "spans": with_spans,
                    "traced": summary.window_ns / 1e9 / n,
                    "traced_plain": summary_plain.window_ns / 1e9 / n},
        "metrics": _read(ctx), "metrics_plain": _read(ctx_plain),
        "span_ms": {"spans": spans_host, "traced": traced_host},
        "idle_gaps": trace.idle_gaps(
            summary, names=harness.SPANS + program_spans.FED_SPANS),
        "idle_gaps_plain": trace.idle_gaps(summary_plain,
                                           names=harness.SPANS),
        "counters": {k: v["points"] for k, v in counters.items()},
    }
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{name}-summary.json", "w") as f:
        json.dump(recorded(summary, counters, n), f)
    del b, run, summary, summary_plain
    gc.collect()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="chiprun_out/host_spans")
    args = ap.parse_args(argv)
    bench_run._prepare()
    for name in args.workload:
        print(json.dumps(measure(name, args.seed, Path(args.out))),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
