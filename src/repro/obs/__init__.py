"""repro.obs — observability for the federated edge runtime.

Span tracing on the simulated clock (:mod:`repro.obs.trace`), a
counters/gauges/histograms registry plus the plan==ledger
:class:`PlanAudit` (:mod:`repro.obs.metrics`), and exporters — JSONL,
CSV, Perfetto-loadable Chrome trace JSON, ``BENCH_*.json``
(:mod:`repro.obs.export`).

Attach a :class:`Tracer` to a run::

    from repro import obs
    tracer = obs.Tracer()
    run = FederatedRun(mcfg, fcfg, train, test, "fim_lbfgs", tracer=tracer)
    run.run(rounds=8)
    obs.write_chrome(tracer, "trace.json")       # load in ui.perfetto.dev
    obs.write_jsonl(tracer, "trace.jsonl")
    tracer.audit.verify(run.ledger)              # plan == ledger, audited

The round's host steps (``fed.round``, ``fed.client``, ``fed.put`` …)
are ``Tracer.wall_span`` blocks, each a ``jax.profiler.TraceAnnotation``:
under ``jax.profiler.trace`` they sit beside the device ops.  A tracer
can be attached for some rounds only (``run.tracer = tracer``, then
``run.tracer = None``).

The default is :data:`NULL_TRACER` — a shared no-op — so the
instrumented hot path costs nothing when tracing is off.
"""
from repro.obs.export import (metrics_to_csv, parse_jsonl, to_chrome,
                              to_jsonl, write_bench_json, write_chrome,
                              write_jsonl, write_metrics_csv)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               NULL_AUDIT, NULL_METRICS, PlanAudit,
                               reason_key)
from repro.obs.trace import (AGGREGATE, ALLOCATE, CAT_ASYNC, CAT_CLIENT,
                             CAT_ROUND, CAT_WALL, COMPUTE, DISPATCH, DOWNLINK,
                             EXPIRE, FAULT, LAND, NULL_TRACER, REALLOC, ROUND,
                             UPLINK, VERDICT, NullTracer, Span, TraceEvent,
                             Tracer, render_round)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "PlanAudit",
    "NULL_AUDIT", "NULL_METRICS", "NULL_TRACER", "NullTracer", "Span",
    "TraceEvent", "Tracer", "render_round", "reason_key",
    "metrics_to_csv", "parse_jsonl", "to_chrome", "to_jsonl",
    "write_bench_json", "write_chrome", "write_jsonl", "write_metrics_csv",
    "AGGREGATE", "ALLOCATE", "CAT_ASYNC", "CAT_CLIENT", "CAT_ROUND",
    "CAT_WALL", "COMPUTE", "DISPATCH", "DOWNLINK", "EXPIRE", "FAULT",
    "LAND", "REALLOC", "ROUND", "UPLINK", "VERDICT",
]
