"""The command refuses to run where it cannot measure."""
import json
import os
import shutil
import subprocess
import sys

from fedbench import manifest


def _run(cwd, script):
    env = {k: v for k, v in os.environ.items()}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, script, "--workload", "fmnist-fimlbfgs", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(manifest.ROOT, "bench/run.py")
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not proc.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(manifest.MANIFEST, tmp_path / "BENCHMARK.json")
    shutil.copytree(manifest.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path, "bench/run.py")
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
