"""Benchmark driver: one module per paper table/figure + kernel microbench +
the roofline table (from existing dry-run artifacts).  Prints
``name,us_per_call,derived``-style CSVs and writes copies to experiments/.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only tableX]
"""
from __future__ import annotations

import argparse
import time

from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (hours); default is quick mode")
    ap.add_argument("--only", default=None, help="run a single benchmark")
    args = ap.parse_args()
    quick = not args.full

    from benchmarks import (edge_tradeoff, fig4_hyperparams, kernels_bench,
                            roofline, table2_optimizers, table3_noniid,
                            table4_datasharing, table5_clients,
                            thm3_comm_cost)

    benches = {
        "table2": lambda: table2_optimizers.run(quick),
        "table3": lambda: table3_noniid.run(quick),
        "table4": lambda: table4_datasharing.run(quick),
        "table5": lambda: table5_clients.run(quick),
        "fig4": lambda: fig4_hyperparams.run(quick),
        "thm3": lambda: thm3_comm_cost.run(quick),
        "edge": lambda: edge_tradeoff.run(quick),
        "kernels": lambda: kernels_bench.run(quick),
        "roofline": roofline.run,
    }
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        fn()
        print(f"[{name}] done in {time.time()-t0:.1f}s\n")


if __name__ == "__main__":
    main()
