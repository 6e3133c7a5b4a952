"""From a profiler trace to the numbers the per-layer readers take.

``summarize`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps what the readers need, as plain lists (``Summary``), so that the
reduction can be checked on a small recorded trace without a chip:

* per device plane (``/device:TPU:<n>``): every op on its ``XLA Ops``
  line, as [name, program, start, duration, text], and every program
  execution on its ``XLA Modules`` line, as [program, start, duration].
  An op's name is its HLO instruction's (``fim_diag.35``), its text the
  instruction up to 200 characters, and its program the execution whose
  interval holds it (the trace gives ops no program of their own);
* the host's annotations (``jax.profiler.TraceAnnotation``) named in
  ``names``, which say what the host was doing;
* the traced window: the span of the annotation ``WINDOW``.

All times are nanoseconds on the profiler's clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.traced_rounds"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TEXT_CHARS = 200


@dataclass
class Summary:
    window: tuple                                  # (start_ns, end_ns)
    ops: list = field(default_factory=list)        # per device
    modules: list = field(default_factory=list)    # per device
    spans: list = field(default_factory=list)      # host: [name, start, dur]

    @property
    def window_ns(self) -> float:
        return float(self.window[1] - self.window[0])

    @classmethod
    def from_dict(cls, d: dict) -> "Summary":
        return cls(window=tuple(d["window"]), ops=d["ops"],
                   modules=d["modules"], spans=d["spans"])


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(text: str) -> str:
    """``%fim_diag.35 = f32[...] custom-call(...)`` -> ``fim_diag.35``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def result_type(text: str) -> str:
    """The op's result type, ``f32[512,2359296]``, without its layout."""
    rhs = text.split(" = ", 1)[1] if " = " in text else ""
    return rhs.split("{", 1)[0].split(" ", 1)[0]


def assign_modules(ops: list, modules: list) -> None:
    """Give each op [name, "", start, dur, text] the program whose
    execution interval holds its start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op[2]) - 1
        if i >= 0 and op[2] < mods[i][1] + mods[i][2]:
            op[1] = mods[i][0]


def summarize(path: str, names=()) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    keep = set(names) | {WINDOW}
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[
                len(DEVICE_PREFIX):].isdigit():
            dev_ops, dev_mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev_ops = [[op_name(e.name), "", e.start_ns,
                                e.duration_ns, e.name[:TEXT_CHARS]]
                               for e in line.events]
                elif line.name == MODULES_LINE:
                    dev_mods = [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events]
            assign_modules(dev_ops, dev_mods)
            ops.append(dev_ops)
            modules.append(dev_mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events if e.name in keep)
    wins = [s for s in spans if s[0] == WINDOW]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found {len(wins)}")
    window = (wins[0][1], wins[0][1] + wins[0][2])
    return Summary(window=window, ops=ops, modules=modules, spans=spans)


def program(module: str) -> str:
    """``jit_gram(14779855184252225376)`` -> ``jit_gram``."""
    return module.split("(", 1)[0]


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------
def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(s + d, hi)) for s, d in intervals
                if s < hi and s + d > lo)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(summary: Summary) -> float:
    """Nanoseconds in which an op ran on the device, averaged over the
    devices traced."""
    lo, hi = summary.window
    per_dev = [sum(e - s for s, e in union([(o[2], o[3]) for o in dev],
                                           lo, hi))
               for dev in summary.ops]
    return sum(per_dev) / len(per_dev) if per_dev else 0.0


def idle_share(summary: Summary) -> float | None:
    """1 - busy / window, or None where no device op was traced."""
    if not summary.ops or not any(summary.ops):
        return None
    return 1.0 - busy_ns(summary) / summary.window_ns


def in_window(summary: Summary, start: float, dur: float) -> bool:
    lo, hi = summary.window
    return start >= lo and start + dur <= hi


def module_runs(summary: Summary, match) -> list:
    """[(start, dur)] of the program executions whose module name
    satisfies ``match``, inside the window, over all devices."""
    return [(m[1], m[2]) for dev in summary.modules for m in dev
            if match(m[0]) and in_window(summary, m[1], m[2])]


def op_events(summary: Summary, match) -> list:
    """Ops [name, program, start, dur, text] inside the window that
    satisfy ``match(op)``, over all devices."""
    return [o for dev in summary.ops for o in dev
            if in_window(summary, o[2], o[3]) and match(o)]


def roofline(flops: float, nbytes: float, seconds: float,
             peak_flops: float, peak_bytes_per_s: float):
    """-> (share of the roofline in %, the bound that binds): the least
    time the chip could take, over the time taken."""
    t_compute = flops / peak_flops
    t_memory = nbytes / peak_bytes_per_s
    bound = "memory" if t_memory >= t_compute else "compute"
    return 100.0 * max(t_compute, t_memory) / seconds, bound


def top_ops(summary: Summary, n: int = 10) -> list:
    """The n device ops (by program and name) that took most time."""
    tot: dict = {}
    for o in op_events(summary, lambda o: True):
        key = f"{program(o[1]) or '?'}/{o[0]} {result_type(o[4])}"
        tot[key] = tot.get(key, 0) + o[3]
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    ndev = max(1, len(summary.ops))
    return [[k, v / ndev / 1e9] for k, v in ranked]


def idle_gaps(summary: Summary, n: int = 10, names=None) -> list:
    """The n longest gaps between device ops inside the window (on the
    first device), each named by the innermost host span of ``names``
    that covers its middle, or by "round_loop" (the round loop's own host
    work) where none does."""
    if not summary.ops:
        return []
    lo, hi = summary.window
    busy = union([(o[2], o[3]) for o in summary.ops[0]], lo, hi)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    spans = [s for s in summary.spans
             if names is None or s[0] in names]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        covering = [sp for sp in spans if sp[1] <= mid <= sp[1] + sp[2]]
        name = (min(covering, key=lambda sp: sp[2])[0] if covering
                else "round_loop")
        out.append([name, (e - s) / 1e9])
    return out
