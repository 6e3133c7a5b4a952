"""Drive one cell of the federated benchmark: build, warm up, measure,
trace, and check the result against the plain reference.

The timed entry is the program's ``FederatedRun.round()``, driven in a
closed loop: the next round starts when the last has ended on the
device.  ``FederatedRun.evaluate()`` runs every ``eval_every`` rounds, as
``FederatedRun.run`` calls it.  The program gets the generated data, its
configuration and the weights; it is never told that it is measured.
"""
from __future__ import annotations

import contextlib
import gc
import math
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field

import jax
import numpy as np

from . import compare, data, manifest, trace

# host spans around the calls into each layer (traced run only)
SPANS = ("client_step", "compress_payload", "aggregate", "server_step",
         "evaluate")


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ---------------------------------------------------------------------------
# device and compile accounting
# ---------------------------------------------------------------------------
class CompileClock:
    """Counts from ``jax.monitoring``: programs lowered, backend compiles,
    and persistent-cache hits (a hit still reports a backend compile
    event, so programs compiled from scratch are compiles - hits)."""

    _LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.lowered = self.compiled = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self._LOWER:
            self.lowered += 1
        elif event == self._COMPILE:
            self.compiled += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == self._HIT:
            self.hits += 1

    def snapshot(self) -> tuple:
        return (self.lowered, self.compiled, self.hits)

    def since(self, snap: tuple) -> dict:
        lowered, compiled, hits = (a - b for a, b in zip(
            self.snapshot(), snap, strict=True))
        return {"lowered": lowered, "cache_hits": hits,
                "compiled": compiled - hits}


class GcClock:
    """Pauses of Python's garbage collector, by generation."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = 0.0
        self._t0 = 0.0

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.count[info["generation"]] += 1
            self.seconds += time.perf_counter() - self._t0


class NoChip(RuntimeError):
    pass


def check_device(chips: int):
    """The TPU devices the cell asks for; raises on any other backend,
    on too few chips, and where the program's kernels would not run
    natively."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r} "
                     f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    from repro.kernels import ops
    if ops.resolve("auto") != "native":
        raise NoChip(f"kernels resolve to {ops.resolve('auto')!r}")
    return devs[:chips]


def load_peaks(kind: str) -> dict:
    table = manifest.load_json(manifest.BENCH_DIR / "peaks.json")
    if kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json; "
                       f"known: {sorted(table['kinds'])}")
    return table["kinds"][kind]


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the cell
# ---------------------------------------------------------------------------
@dataclass
class Built:
    run: object
    cell: manifest.Cell
    params0: dict
    x_train: np.ndarray
    y_train: np.ndarray
    timings: dict = field(default_factory=dict)


def fed_config(cell: manifest.Cell, seed: int):
    from repro.configs.base import FedConfig
    t = cell.traffic
    return FedConfig(
        num_clients=t["num_clients"], participation=t["participation"],
        noniid_l=t["noniid_l"], local_epochs=t["local_epochs"],
        batch_size=cell.workload["client_examples"], lbfgs_m=t["lbfgs_m"],
        second_order_lr=t["second_order_lr"],
        max_step_norm=t["max_step_norm"], fim_damping=t["fim_damping"],
        fim_ema=t["fim_ema"], compress=t["compress"], fim_mode=t["fim_mode"],
        kernels=t["kernels"], seed=seed)


def check_partition(partition, y, cell: manifest.Cell) -> None:
    """The non-IID partition as the cell states it: every client holds
    ``client_examples`` examples of at most ``noniid_l`` labels (the
    program's partitioner can deal a client two shards of one label),
    and no example is dealt twice.  A second client size would compile
    inside the window, so the run stops here instead."""
    sizes = Counter(len(p) for p in partition)
    want = cell.workload["client_examples"]
    if set(sizes) != {want}:
        raise ValueError(f"client sizes {dict(sizes)}; the cell states "
                         f"{want} for every client")
    flat = np.concatenate(partition)
    if len(np.unique(flat)) != len(flat):
        raise ValueError("an example is dealt to two clients")
    ell = cell.traffic["noniid_l"]
    labels = Counter(len(np.unique(y[p])) for p in partition)
    if max(labels) > ell:
        raise ValueError(f"clients hold {dict(labels)} labels, over {ell}")


def build(cell: manifest.Cell, seed: int) -> Built:
    from repro.data.synthetic import Dataset
    from repro.fed.server import FederatedRun

    cfg, t = cell.config, cell.traffic
    family = cell.family()
    t0 = time.perf_counter()
    x_tr, y_tr, x_te, y_te = data.make_images(
        seed, cfg["input_shape"], cfg["num_classes"], cfg["train_images"],
        cfg["test_images"], t["image_noise"])
    t1 = time.perf_counter()
    train = Dataset(x_tr, y_tr, cfg["num_classes"], cfg["dataset"])
    test = Dataset(x_te, y_te, cfg["num_classes"], cfg["dataset"])
    run = FederatedRun(family.program_config(cfg), fed_config(cell, seed),
                       train, test, t["algorithm"])
    check_partition(run.partition, y_tr, cell)
    params0 = family.init(cfg, data.key_from_seed(seed, stream=1))
    mine = jax.tree.map(lambda a: (a.shape, a.dtype), params0)
    theirs = jax.tree.map(lambda a: (a.shape, a.dtype), run.strategy.params)
    if mine != theirs:
        raise ValueError("the program's parameters are not laid out as "
                         f"the configuration states: {theirs} != {mine}")
    run.strategy.load_state_dict({"params": params0})
    _check_optimizer(run, t)
    jax.block_until_ready(run.strategy.state_dict())
    return Built(run=run, cell=cell, params0=params0, x_train=x_tr,
                 y_train=y_tr,
                 timings={"data_s": t1 - t0,
                          "build_s": time.perf_counter() - t1})


def _check_optimizer(run, t: dict) -> None:
    """The program's optimizer runs as the traffic file states."""
    ocfg = getattr(run.strategy, "ocfg", None)
    if ocfg is None:
        return
    stated = {"learning_rate": t["second_order_lr"], "m": t["lbfgs_m"],
              "damping": t["fim_damping"], "fim_ema": t["fim_ema"],
              "max_step_norm": t["max_step_norm"],
              "rel_damping": t["rel_damping"],
              "curvature_eps": t["curvature_eps"], "kernels": t["kernels"]}
    got = {k: getattr(ocfg, k) for k in stated}
    if got != stated:
        raise ValueError(f"the program's optimizer runs {got}, the "
                         f"traffic states {stated}")


@contextlib.contextmanager
def patched(obj, name: str, value):
    """Set ``obj.name`` to ``value`` for the block, then restore exactly
    what was there (an instance attribute, or none)."""
    missing = object()
    before = vars(obj).get(name, missing)
    setattr(obj, name, value)
    try:
        yield
    finally:
        if before is missing:
            delattr(obj, name)
        else:
            setattr(obj, name, before)


def _sync(run) -> None:
    jax.block_until_ready(run.strategy.state_dict())


# ---------------------------------------------------------------------------
# warm-up, and the record of the rounds the reference follows
# ---------------------------------------------------------------------------
# Every shape the window uses: the one client size, the evaluation, and the
# server step with an empty and with a non-empty history.
WARMUP_ROUNDS = 2


class Recorder:
    """What the reference needs of the program's first ``follow`` rounds,
    taken through the window's own calls while they run: each round's
    sampled clients, the aggregate the optimizer received in round 1, and
    the parameters after round ``follow``.  The rounds span the warm-up
    and the start of the measured window, so the reference follows the
    optimizer's state as the window holds it."""

    def __init__(self, run, follow: int):
        self.run, self.follow = run, follow
        self.cohorts: list = []
        self.aggregate1 = None
        self.params = None
        self.steps = 0

    def _sample(self, sample):
        def recording_sample():
            ids = sample()
            if len(self.cohorts) < self.follow:
                self.cohorts.append([int(i) for i in ids])
            return ids
        return recording_sample

    def _step(self, server_step):
        def recording_server_step(agg):
            if self.aggregate1 is None:
                self.aggregate1 = agg
            out = server_step(agg)
            self.steps += 1
            if self.steps == self.follow:
                self.params = self.run.strategy.params
            return out
        return recording_server_step

    @contextlib.contextmanager
    def recording(self):
        run = self.run
        with patched(run, "sample_clients", self._sample(run.sample_clients)), \
                patched(run.strategy, "server_step",
                        self._step(run.strategy.server_step)):
            yield self

    def readings(self, losses: list) -> dict:
        """What the program produced in the followed rounds, on the host:
        ``losses`` are the rounds' losses in order."""
        grad1, fisher1 = self.aggregate1
        return {"loss": list(losses[:self.follow]), "grad1": _host(grad1),
                "fisher1": _host(fisher1), "params": _host(self.params)}


def rounds(run, first_round: int, n: int, traffic: dict) -> list:
    """``n`` rounds through the window's own calls; their losses."""
    return [_one_round(run, t, traffic["eval_every"], traffic["eval_examples"])
            for t in range(first_round, first_round + n)]


def warmup(run, traffic: dict) -> list:
    """The warm-up rounds and one evaluation; the rounds' losses."""
    losses = [run.round().get("loss", math.nan)
              for _ in range(WARMUP_ROUNDS)]
    run.evaluate(traffic["eval_examples"])
    _sync(run)
    return losses


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------
def _one_round(run, t: int, eval_every: int, eval_examples: int) -> float:
    info = run.round()
    if t % eval_every == 0:
        run.evaluate(eval_examples)
    _sync(run)
    return info.get("loss", math.nan)


def window(run, seconds: float, first_round: int, traffic: dict) -> dict:
    """Rounds in a closed loop until the first round boundary after
    ``seconds``.  Returns the wall time, each round's time and loss."""
    times, losses = [], []
    t = first_round
    start = time.perf_counter()
    while True:
        r0 = time.perf_counter()
        losses.append(_one_round(run, t, traffic["eval_every"],
                                 traffic["eval_examples"]))
        now = time.perf_counter()
        times.append(now - r0)
        t += 1
        if now - start >= seconds:
            break
    return {"wall_s": now - start, "rounds": len(times), "times": times,
            "losses": losses, "next_round": t}


def traced_rounds(run, n: int, first_round: int, traffic: dict,
                  logdir: str) -> trace.Summary:
    """``n`` rounds under the profiler, with a host span around each call
    into a layer, so that idle gaps can be named."""
    annotate = jax.profiler.TraceAnnotation
    with contextlib.ExitStack() as stack:
        for name in SPANS:
            owner = run if name == "evaluate" else run.strategy
            fn = getattr(owner, name, None)
            if fn is None:
                continue

            def spanned(*a, _fn=fn, _name=name, **k):
                with annotate(_name):
                    return _fn(*a, **k)

            stack.enter_context(patched(owner, name, spanned))
        # no Python tracer (it records every Python call) and only the
        # host's critical events, the annotations among them: more slows
        # the host and fills the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            with annotate(trace.WINDOW):
                for t in range(first_round, first_round + n):
                    _one_round(run, t, traffic["eval_every"],
                               traffic["eval_examples"])
        finally:
            jax.profiler.stop_trace()
    return trace.summarize(trace.find_xplane(logdir), names=SPANS)


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


def cohort_data(cohorts, partition, x, y) -> list:
    return [[(x[partition[k]], y[partition[k]]) for k in ids]
            for ids in cohorts]


def reference(cell: manifest.Cell, params0, cohorts, dtype="float32",
              fault: str | None = None) -> dict:
    algo = cell.algorithm()
    with jax.default_matmul_precision("highest"):
        return algo.reference_rounds(cell.family(), cell.config,
                                     cell.traffic, params0, cohorts,
                                     dtype=dtype, fault=fault)


@dataclass
class Context:
    """What a metric reader reads."""
    cell: manifest.Cell
    setup_s: float
    window: dict
    peaks: dict | None
    chips: int
    trace: trace.Summary | None = None


def read_metrics(metrics: list, ctx: Context) -> dict:
    out = {}
    for m in metrics:
        value = m.reader().read(ctx)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out


def run_cell(cell: manifest.Cell, seed: int, seconds: float, traced: bool,
             t_start: float) -> dict:
    """One run of one cell; returns the result object."""
    devices = check_device(cell.chips)
    dev = devices[0]
    peaks = load_peaks(dev.device_kind)
    clock = CompileClock()
    t_init = time.perf_counter() - t_start

    b = build(cell, seed)
    c_build = clock.compile_s
    sizes = Counter(len(p) for p in b.run.partition)
    labels = Counter(len(np.unique(b.y_train[p])) for p in b.run.partition)
    log(f"cell={cell.name} seed={seed} device={dev.device_kind} "
        f"chips={len(devices)} client_sizes={dict(sizes)} "
        f"labels_per_client={dict(labels)} "
        f"params={b.run.strategy.n_params()}")
    rec = Recorder(b.run, cell.workload["reference_rounds"])
    with rec.recording():
        t0 = time.perf_counter()
        losses = warmup(b.run, cell.traffic)
        t_warm = time.perf_counter() - t0
        setup_s = time.perf_counter() - t_start
        log(f"setup_s={setup_s:.4f} import_and_init_s={t_init:.4f} "
            f"data_s={b.timings['data_s']:.4f} "
            f"build_s={b.timings['build_s']:.4f} warmup_s={t_warm:.4f} "
            f"compile_s_in_data_and_build={c_build:.4f} "
            f"compile_s_in_warmup={clock.compile_s - c_build:.4f}")

        snap = clock.snapshot()
        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        try:
            win = window(b.run, seconds, WARMUP_ROUNDS + 1, cell.traffic)
        finally:
            gc.callbacks.remove(gc_clock)
        counts = clock.since(snap)
        # a window too short for the rounds the reference follows: the
        # rest run after it, untimed
        extra = rec.follow - rec.steps
        if extra > 0:
            win["losses_after"] = rounds(b.run, win["next_round"], extra,
                                         cell.traffic)
            win["next_round"] += extra
    log(f"window rounds={win['rounds']} wall_s={win['wall_s']:.4f} "
        f"compiles_in_window={counts['compiled']} "
        f"cache_fetches_in_window={counts['cache_hits']} "
        f"lowered_in_window={counts['lowered']} "
        f"gc_collections_by_generation={gc_clock.count} "
        f"gc_s={gc_clock.seconds:.4f}")
    log("round_times_s=" + " ".join(f"{t:.4f}" for t in win["times"]))

    summary = None
    if traced:
        with tempfile.TemporaryDirectory() as logdir:
            summary = traced_rounds(b.run, cell.traffic["trace_rounds"],
                                    win["next_round"], cell.traffic, logdir)
    peak = memory_peak(devices)
    log(f"memory_peak_bytes={peak}")

    mine = rec.readings(losses + win["losses"] + win.get("losses_after", []))
    cohorts = cohort_data(rec.cohorts, b.run.partition, b.x_train, b.y_train)
    params0 = jax.tree.map(np.asarray, b.params0)
    del b, rec
    gc.collect()
    log(f"reference_rounds={len(cohorts)} bytes_in_use_before_reference="
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")

    t0 = time.perf_counter()
    ref = reference(cell, params0, cohorts)
    values = compare.readings(mine, ref, params0)
    log(f"reference_s={time.perf_counter() - t0:.4f}")
    correct, rows = compare.judge(values, cell.workload.get("limits", {}))

    ctx = Context(cell=cell, setup_s=setup_s, window=win, peaks=peaks,
                  chips=len(devices), trace=summary)
    metrics = read_metrics(cell.per_layer() if traced else cell.end_to_end(),
                           ctx)
    failed = sum(1 for v in win["losses"] if not math.isfinite(v))
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": win["rounds"],
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if summary is not None:
        result["device"]["busy_s"] = trace.busy_ns(summary) / 1e9
        result["device"]["window_s"] = summary.window_ns / 1e9
        result["breakdown"] = {
            "device_ops": trace.top_ops(summary),
            "idle_gaps": trace.idle_gaps(summary, names=SPANS)}
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ in rows}
    return result


def report_checks(result: dict) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for name, c in result["checks"].items():
        ok = c["limit"] is None or c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr, flush=True)
