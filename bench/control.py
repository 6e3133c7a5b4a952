#!/usr/bin/env python3
"""Readings that set a cell's limits: the program, the control and the
planted faults, on many seeds in one process (so compiled programs are
shared).  Needs the chip; the benchmark's own runs never run this.

    python3 bench/control.py --workload <cell> --seeds 11 12 13 ... \
        [--control-seeds 3]

For each seed it builds the cell as a run does, drives the program
through the rounds the reference follows (the warm-up and the first of
the window's), and compares against the float32 reference:

* ``program``: the program itself (sound runs set the lower reading);
* ``control``: the reference in the program's place, its products in
  float8 (e4m3, per-tensor scale), the step below the bfloat16 products
  that the configuration's float32 at default precision runs on a TPU;
* ``bfloat16``: the reference in the program's place, its client work in
  bfloat16 (read for the record: on a TPU it matches the program);
* the planted faults of ``bench/algorithms/fim_lbfgs.FAULTS``, each the
  reference in the program's place with one fault: ``half_batch`` (half
  of each client's batch left out, the mean over the rest),
  ``fisher_x2`` (each client's Fisher diagonal doubled where it is
  produced) and, where the reference follows more than m+1 rounds,
  ``stale_history`` (the history stops taking pairs once it holds m).

A state left unchanged reads 1 on ``delta`` by construction and needs
no run.  The first ``--control-seeds`` seeds also read the control and
the faults.  One JSON line per seed and reading goes to standard output.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    bench_run._prepare()
    import jax
    import numpy as np

    from fedbench import compare, harness, manifest

    cell = manifest.load_cell(args.workload)
    harness.check_device(cell.chips)
    follow = cell.workload["reference_rounds"]
    faults = ["half_batch", "fisher_x2"]
    if follow > cell.traffic["lbfgs_m"] + 1:
        faults.append("stale_history")
    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        b = harness.build(cell, seed)
        rec = harness.Recorder(b.run, follow)
        with rec.recording():
            losses = harness.warmup(b.run, cell.traffic)
            losses += harness.rounds(b.run, harness.WARMUP_ROUNDS + 1,
                                     follow - harness.WARMUP_ROUNDS,
                                     cell.traffic)
        mine = rec.readings(losses)
        cohorts = harness.cohort_data(rec.cohorts, b.run.partition,
                                      b.x_train, b.y_train)
        params0 = jax.tree.map(np.asarray, b.params0)
        t_prog = time.perf_counter() - t0
        del b, rec
        gc.collect()
        t0 = time.perf_counter()
        ref = harness.reference(cell, params0, cohorts)
        t_ref = time.perf_counter() - t0
        out = {"program": mine}
        if i < args.control_seeds:
            out["control"] = harness.reference(cell, params0, cohorts,
                                               dtype="float8_e4m3fn")
            out["bfloat16"] = harness.reference(cell, params0, cohorts,
                                                dtype="bfloat16")
            for fault in faults:
                out[fault] = harness.reference(cell, params0, cohorts,
                                               fault=fault)
        for kind, got in out.items():
            values = compare.readings(got, ref, params0)
            print(json.dumps({"cell": cell.name, "seed": seed, "kind": kind,
                              "values": values, "program_s": t_prog,
                              "reference_s": t_ref}), flush=True)
        del out, ref, cohorts
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
