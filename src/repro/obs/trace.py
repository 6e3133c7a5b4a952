"""Typed span/event tracing on the simulated ``EventClock`` timeline.

One traced ``FederatedRun`` answers "where did round 37's time, energy,
and bytes go, and why was client 12 dropped?" without a debugger: every
round is a span, every selected client gets child spans for
allocate → compute → uplink → deadline-verdict → aggregate, and the
async path emits dispatch / land / expiry events.  Span times are
*simulated seconds* (the edge clock); wall-clock measurements (codec
encode time, ``wall_span`` blocks) live on a separate timeline so
replays of the same seed stay bit-identical on the sim tracks.

``wall_span`` is also the host side of the device trace: on an enabled
tracer each block is a ``jax.profiler.TraceAnnotation``, so under
``jax.profiler.trace`` the federated round's host steps (``fed.round``,
``fed.client``, ``fed.put`` …) land in the same ``.xplane.pb`` as the
device ops.  While a tracer is attached to a run, JAX's compile events
are counted in its registry (``jax_lowered_total``,
``jax_cache_hits_total``, ``jax_compiled_total``), labelled by the
innermost open ``wall_span``.

The default everywhere is :data:`NULL_TRACER` — a shared no-op whose
methods early-out and whose ``metrics`` / ``audit`` are the no-op twins
from :mod:`repro.obs.metrics` — so the instrumented hot path costs one
attribute check when tracing is off, and ``tests/test_determinism.py``
replays are unchanged.

Exports live in :mod:`repro.obs.export`: JSONL event log, CSV metric
summaries, and Chrome trace-event JSON loadable in Perfetto.
"""
from __future__ import annotations

import contextlib
import math
import time
import weakref
from dataclasses import dataclass, field

from repro.obs import metrics as _metrics

# span/event categories (Chrome trace "cat", JSONL "cat")
CAT_ROUND = "round"     # round-level phases on the sim timeline
CAT_CLIENT = "client"   # per-client phases on the sim timeline
CAT_ASYNC = "async"     # buffered-async dispatch / land / expiry
CAT_WALL = "wall"       # host wall-clock measurements (non-deterministic)

# canonical span / event names
ALLOCATE = "allocate"
COMPUTE = "compute"
UPLINK = "uplink"
VERDICT = "deadline_verdict"
AGGREGATE = "aggregate"
DOWNLINK = "downlink"
ROUND = "round"
DISPATCH = "dispatch"
LAND = "land"
EXPIRE = "expire"
FAULT = "scenario_fault"
REALLOC = "reallocate"


@dataclass(frozen=True)
class Span:
    """A closed interval on a timeline (simulated seconds unless
    ``cat == CAT_WALL``)."""
    name: str
    cat: str
    t0: float
    t1: float
    round_id: int = -1
    client: int = -1
    args: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.t1 - self.t0


@dataclass(frozen=True)
class TraceEvent:
    """An instant on a timeline."""
    name: str
    cat: str
    t: float
    round_id: int = -1
    client: int = -1
    args: dict = field(default_factory=dict)


def render_round(rec: dict) -> str:
    """The console form of one per-round log record — byte-compatible
    with the pre-tracer ``FederatedRun.run`` progress print."""
    return (f"round {rec.get('round', 0):4d} "
            f"loss {rec.get('loss', float('nan')):.4f} "
            f"acc {rec.get('accuracy', float('nan')):.4f}")


# one shared no-op block: an untraced ``wall_span`` builds nothing
_NULL_SPAN = contextlib.nullcontext()


class NullTracer:
    """The no-op default: every hook early-outs, the console sink still
    renders per-round progress when asked (so ``verbose=`` keeps working
    without a real tracer attached)."""

    enabled = False

    def __init__(self):
        self.metrics = _metrics.NULL_METRICS
        self.audit = _metrics.NULL_AUDIT

    # -- recording hooks (all no-ops here) -------------------------------
    def span(self, name: str, cat: str, t0: float, t1: float,
             round_id: int = -1, client: int = -1, **args) -> None:
        pass

    def event(self, name: str, cat: str, t: float,
              round_id: int = -1, client: int = -1, **args) -> None:
        pass

    def record_round(self, rec: dict) -> None:
        pass

    def log_round(self, rec: dict, render: bool = False) -> None:
        if render:
            print(render_round(rec))

    def wall_span(self, name: str, round_id: int = -1, client: int = -1,
                  **args):
        return _NULL_SPAN

    def count(self, name: str, value: float = 1.0, **labels) -> None:
        pass

    def attach(self) -> None:
        pass

    def detach(self) -> None:
        pass


NULL_TRACER = NullTracer()


class _OpenSpan:
    """A ``wall_span`` block; ``dur`` (host seconds) is set when it
    closes."""
    __slots__ = ("name", "round_id", "client", "dur")

    def __init__(self, name: str, round_id: int, client: int):
        self.name, self.round_id, self.client = name, round_id, client
        self.dur = math.nan


class Tracer(NullTracer):
    """Records spans, events, per-round records, and structured logs;
    owns a live :class:`~repro.obs.metrics.MetricsRegistry` and
    :class:`~repro.obs.metrics.PlanAudit`.

    ``sink`` is the console sink for rendered per-round log lines
    (default: ``print``); pass a list's ``append`` or any callable to
    capture them.  ``wall_span`` blocks always go onto the profiler's
    clock (a ``jax.profiler.TraceAnnotation`` each, seen only under
    ``jax.profiler.trace``); ``wall=True`` additionally records them in
    memory on the host wall-clock timeline (category ``CAT_WALL`` —
    excluded from determinism comparisons by construction, since sim and
    wall categories never mix).  ``audit_max_rows`` caps
    :class:`~repro.obs.metrics.PlanAudit` row retention for fleet-scale
    runs (None = exhaustive; totals stay exact and shortfall rows are
    always kept either way)."""

    enabled = True

    def __init__(self, wall: bool = False, sink=None, audit_max_rows=None):
        from jax.profiler import TraceAnnotation
        self.metrics = _metrics.MetricsRegistry()
        self.audit = _metrics.PlanAudit(max_rows=audit_max_rows)
        self.spans: list[Span] = []
        self.events: list[TraceEvent] = []
        self.records: list[dict] = []   # per-round edge runtime records
        self.logs: list[dict] = []      # per-round driver log records
        self.wall = bool(wall)
        self._sink = print if sink is None else sink
        self._annotate = TraceAnnotation
        self._open: list[_OpenSpan] = []   # wall_span blocks, innermost last
        self._attached = 0                 # runs this tracer is attached to
        # CAT_WALL epoch: wall measurement is the opt-in exception to
        # the sim-determinism contract
        self._wall_epoch = time.perf_counter()  # repro: allow[RPL001]

    # -- recording -------------------------------------------------------
    def span(self, name: str, cat: str, t0: float, t1: float,
             round_id: int = -1, client: int = -1, **args) -> None:
        self.spans.append(Span(name, cat, float(t0), float(t1),
                               int(round_id), int(client), args))

    def event(self, name: str, cat: str, t: float,
              round_id: int = -1, client: int = -1, **args) -> None:
        self.events.append(TraceEvent(name, cat, float(t),
                                      int(round_id), int(client), args))

    def record_round(self, rec: dict) -> None:
        self.records.append(dict(rec))

    def log_round(self, rec: dict, render: bool = False) -> None:
        self.logs.append({k: v for k, v in rec.items()
                          if _jsonable(v)})
        if render:
            self._sink(render_round(rec))

    @contextlib.contextmanager
    def wall_span(self, name: str, round_id: int = -1, client: int = -1,
                  **args):
        """A host block on the profiler's clock, named ``name`` with
        ``round_id``/``client`` (where given) and ``args`` as its stats.
        Yields an :class:`_OpenSpan` whose ``dur`` is set on exit.  A
        block inherits the round and client of the enclosing block; with
        ``wall=True`` it is also kept in memory as a ``CAT_WALL`` span
        whose ``args`` name its ``parent``."""
        stats = {k: v for k, v in (("round_id", round_id),
                                   ("client", client)) if v >= 0}
        stats.update(args)
        parent = self._open[-1] if self._open else None
        if parent is not None:
            round_id = parent.round_id if round_id < 0 else round_id
            client = parent.client if client < 0 else client
        block = _OpenSpan(name, round_id, client)
        self._open.append(block)
        t0 = time.perf_counter()  # repro: allow[RPL001]
        try:
            with self._annotate(name, **stats):
                yield block
        finally:
            block.dur = time.perf_counter() - t0  # repro: allow[RPL001]
            self._open.pop()
            if self.wall:
                t0 -= self._wall_epoch
                self.span(name, CAT_WALL, t0, t0 + block.dur,
                          round_id=round_id, client=client,
                          parent=parent.name if parent else "", **args)

    def count(self, name: str, value: float = 1.0, **labels) -> None:
        """Add ``value`` to counter ``name``, labelled ``span=`` the
        innermost open ``wall_span`` ("" where none is open)."""
        span = self._open[-1].name if self._open else ""
        self.metrics.counter(name).inc(value, span=span, **labels)

    # -- compile events ----------------------------------------------------
    def attach(self) -> None:
        """Count JAX's compile events from now until :meth:`detach` (a
        run attaches the tracer it is given; see ``FederatedRun.tracer``).
        Attaching twice needs two detaches."""
        self._attached += 1
        if self._attached == 1:
            _ATTACHED.add(self)
            _listen()

    def detach(self) -> None:
        self._attached = max(self._attached - 1, 0)
        if self._attached == 0:
            _ATTACHED.discard(self)
            if not _ATTACHED:
                _listen(False)

    # -- views -----------------------------------------------------------
    def spans_for(self, round_id: int, cat: str = None,
                  client: int = None) -> list[Span]:
        return [s for s in self.spans
                if s.round_id == round_id
                and (cat is None or s.cat == cat)
                and (client is None or s.client == client)]

    def events_named(self, name: str) -> list[TraceEvent]:
        return [e for e in self.events if e.name == name]


# ---------------------------------------------------------------------------
# JAX compile events -> the attached tracers' counters
# ---------------------------------------------------------------------------
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
# attached tracers, held weakly: a tracer dropped with its run stops
# counting without a detach
_ATTACHED: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_listening = False


def _on_compile_duration(event: str, secs: float, fun_name: str = "",
                         **_) -> None:
    """One program lowered (``jax_lowered_total``) or through the backend
    (``jax_compiled_total``: compiled, or fetched from the persistent
    cache, which JAX reports alike), by program name."""
    if event == _LOWER_EVENT:
        name = "jax_lowered_total"
    elif event == _COMPILE_EVENT:
        name = "jax_compiled_total"
    else:
        return
    for tr in tuple(_ATTACHED):
        tr.count(name, program=str(fun_name))


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        for tr in tuple(_ATTACHED):
            tr.count("jax_cache_hits_total")


def _listen(on: bool = True) -> None:
    """Register the compile listeners while some tracer is attached, and
    only then: no listener runs for an untraced program."""
    global _listening
    if on == _listening:
        return
    from jax import monitoring
    if on:
        monitoring.register_event_duration_secs_listener(_on_compile_duration)
        monitoring.register_event_listener(_on_event)
    else:
        monitoring.unregister_event_duration_listener(_on_compile_duration)
        monitoring.unregister_event_listener(_on_event)
    _listening = on


def _jsonable(v) -> bool:
    if isinstance(v, float):
        return True  # NaN handled at export time
    return isinstance(v, (int, str, bool, type(None)))


def sanitize_float(v):
    """NaN/Inf are not valid JSON scalars; stringify them for export."""
    if isinstance(v, float) and not math.isfinite(v):
        return repr(v)
    return v
