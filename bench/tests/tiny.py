"""A CPU-sized cell for the harness's tests: the F-MNIST CNN's layout at
8x8 pixels, 10 clients of 40 examples, the cells' own traffic file with
fewer clients."""
from pathlib import Path

from fedbench import manifest

DATA = Path(__file__).resolve().parent / "data"


TRAFFIC = "fimlbfgs-k100-q02-noniid2"


def traffic() -> dict:
    t = manifest.load_json(manifest.BENCH_DIR / "traffic" / f"{TRAFFIC}.json")
    t.update(num_clients=10, eval_examples=50, trace_rounds=2)
    return t


def cell(limits_from: str | None = None) -> manifest.Cell:
    """The tiny cell; ``limits_from`` takes the limits of a real cell."""
    workload = manifest.load_json(DATA / "tiny-cell.json")
    if limits_from is not None:
        workload["limits"] = manifest.load_json(
            manifest.BENCH_DIR / "workloads"
            / f"{limits_from}.json")["limits"]
    metrics = manifest.metrics_of(manifest.load_json(manifest.MANIFEST))
    return manifest.Cell(
        name=workload["name"], workload=workload,
        config=manifest.load_json(DATA / "tiny-cnn.json"),
        traffic=traffic(),
        metrics=manifest.cell_metrics(metrics, "fmnist-fimlbfgs"))
