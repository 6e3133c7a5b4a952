"""The program's own host spans and counters, as the per-layer readers of
the round's host side take them.

``FederatedRun`` opens a ``fed.*`` block (``repro.obs.Tracer.wall_span``)
around each host step of a round when a tracer is attached, and the
profiler records each as a host annotation beside the device ops; the
tracer's registry counts the bytes copied to the device and JAX's
compile events.  This module names those spans, reduces them, and runs
the harness's traced rounds with a tracer attached, keeping the
program's spans in the ``Summary`` beside the harness's own.

The harness as it stands neither attaches a tracer nor keeps these
spans, so a reader of this module finds nothing in its runs and returns
None; ``bench/host_spans.py`` runs them on the chip.
"""
from __future__ import annotations

from dataclasses import dataclass

from . import harness, trace

ROUND = "fed.round"
CLIENT = "fed.client"
WAIT = "fed.wait"
# every span the program opens in a synchronous round and an evaluation
FED_SPANS = (ROUND, "fed.sample", "fed.gather", CLIENT, "fed.put",
             "fed.dispatch", WAIT, "fed.compress", "fed.aggregate",
             "fed.server_step", "fed.evaluate")


@dataclass
class Context(harness.Context):
    """A reader's context with the attached tracer's counters
    (``MetricsRegistry.as_dict()``) and the number of traced rounds."""
    counters: dict | None = None
    traced_rounds: int | None = None


def spans(summary: trace.Summary, name: str) -> list:
    """[start, dur] of the spans called ``name`` inside the window."""
    return [[s[1], s[2]] for s in summary.spans
            if s[0] == name and trace.in_window(summary, s[1], s[2])]


def mean_ms(ctx, name: str) -> float | None:
    """Mean duration of the spans called ``name``, in ms; None where the
    trace holds none."""
    if ctx.trace is None:
        return None
    durs = [d for _, d in spans(ctx.trace, name)]
    return sum(durs) / len(durs) / 1e6 if durs else None


def host_ms(summary: trace.Summary, outer: str, inner: str) -> float | None:
    """Mean over the ``outer`` spans of their duration less that of the
    ``inner`` spans they hold, in ms."""
    outs = spans(summary, outer)
    if not outs:
        return None
    ins = spans(summary, inner)
    total = 0.0
    for s, d in outs:
        total += d - sum(di for si, di in ins if s <= si and si + di <= s + d)
    return total / len(outs) / 1e6


def counter_total(ctx, name: str) -> float | None:
    """The counter summed over its labels; None where the context has no
    counters or no traced rounds (the harness attached no tracer)."""
    counters = getattr(ctx, "counters", None)
    if not counters or not getattr(ctx, "traced_rounds", None):
        return None
    metric = counters.get(name)
    return sum(v for _, v in metric["points"]) if metric else 0.0


def per_round(ctx, name: str) -> float | None:
    total = counter_total(ctx, name)
    return None if total is None else total / ctx.traced_rounds


def traced_rounds(run, tracer, n: int, first_round: int, traffic: dict,
                  logdir: str) -> trace.Summary:
    """The harness's traced rounds with ``tracer`` attached to the run
    for them alone, keeping the program's spans in the summary."""
    before, run.tracer = run.tracer, tracer
    try:
        with harness.patched(harness, "SPANS", harness.SPANS + FED_SPANS):
            return harness.traced_rounds(run, n, first_round, traffic,
                                         logdir)
    finally:
        run.tracer = before
