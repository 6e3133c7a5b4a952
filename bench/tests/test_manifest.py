"""BENCHMARK.json against the contract's rules, and every name resolved
to its files."""
import json

import pytest

from fedbench import compare, manifest

M = manifest.load_json(manifest.MANIFEST)
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def _names():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in M[section]:
            yield entry["name"]
    for w in M["workloads"]:
        yield w["config"]
        yield w["traffic"]
    for c in M["configs"]:
        yield from c["reduced"]


def test_top_level_keys_and_size():
    assert set(M) == KEYS
    assert manifest.MANIFEST.stat().st_size <= 64 * 1024
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert manifest.NAME_RE.match(name), name


def test_units_and_metric_keys():
    for kind, allowed in (("end_to_end", {"name", "unit", "better", "bound",
                                          "source", "workloads"}),
                          ("per_layer", {"name", "unit", "better", "source",
                                         "layer", "moves", "workloads"})):
        for m in M[kind]:
            assert set(m) <= allowed, m
            assert manifest.UNIT_RE.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in M["end_to_end"]}
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_names_are_unique():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in M[section]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", [w["name"] for w in M["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = manifest.load_cell(cell)
    assert c.workload["why"] == next(w["why"] for w in M["workloads"]
                                     if w["name"] == cell)
    assert len(c.workload["why"]) <= 200
    assert c.config["name"] == c.workload["config"]
    assert c.traffic["name"] == c.workload["traffic"]
    fam = c.family()
    assert fam.n_params(c.config) == c.config["n_params"]
    assert hasattr(c.algorithm(), "reference_rounds")
    # every client holds its share of the training set
    assert c.config["train_images"] == (c.workload["client_examples"]
                                        * c.traffic["num_clients"])
    e2e = [m.name for m in c.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer()
    for m in c.metrics:
        assert callable(m.reader().read)
    assert set(c.workload["limits"]) <= set(compare.NUMBERS)
    assert c.workload["reference_rounds"] >= 1


def test_config_entries_name_their_files():
    for entry in M["configs"]:
        path = manifest.ROOT / entry["file"]
        cfg = json.loads(path.read_text())
        assert cfg["name"] == entry["name"]
        assert set(entry["reduced"]) == set(cfg["reduced"])
        assert entry["file"].startswith(tuple(p + "/" for p in M["paths"]))
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
