#!/usr/bin/env python3
"""Run one cell of the federated benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic and metrics are found by name from
``BENCHMARK.json`` (see ``bench/fedbench/manifest.py``).  The run builds
the program's ``FederatedRun`` on data and weights made from the seed,
warms up, drives rounds in a closed loop for ``--seconds``, and checks
the first rounds against the plain reference.  ``--trace 1`` also
traces a few rounds with the profiler and reports the per-layer metrics
in place of the end-to-end ones.

Earlier lines of standard output say what was run and where set-up went;
the last line is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` when traced, and ``checks``, each
compared number with its limit, last).  The compared numbers also close
standard error.  Without a TPU, or with fewer chips than the cell asks
for, the run exits nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


def _prepare() -> None:
    """Import paths, and JAX's settings before JAX starts: the program
    from this checkout's ``src``, the compile cache inside the checkout
    at a fixed path, no TPU log files outside it."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        sys.exit(f"bench: the program is not in this checkout ({src})")
    sys.path[:0] = [str(BENCH), str(src)]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: one missing bookkeeping (-atime) file makes every later
    # write to the cache fail
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[1])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    _prepare()
    from fedbench import harness, manifest

    cell = manifest.load_cell(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.report_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
