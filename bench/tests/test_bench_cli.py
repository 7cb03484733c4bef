"""``bench/run.py`` refuses to measure anywhere but on a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)
METRICS = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]


def _run(root, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_exits_nonzero_without_a_tpu(cell, trace):
    p = _run(ROOT, "--workload", cell, "--seed", str(2**35 + 1),
             "--seconds", "1", "--trace", trace)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    for name in METRICS + ["correct", "memory_peak_bytes"]:
        assert name not in p.stdout


def test_unknown_workload_exits_nonzero():
    p = _run(ROOT, "--workload", "no.such.cell", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    # BENCHMARK.json and the files under its paths, without the program
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cell = BENCH["workloads"][0]["name"]
    p = _run(str(tmp_path), "--workload", cell, "--seed", "7",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and not p.stdout.strip()
