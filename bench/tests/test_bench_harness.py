"""The harness finds every part of a cell by name, and a new cell is new
files and entries only."""
import json
import os
import shutil
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import geometry as geo  # noqa: E402
from bench import harness  # noqa: E402

BENCH = harness.load_benchmark(ROOT)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_name_of_a_cell_resolves(cell):
    w = harness.workload(BENCH, cell)
    cfg = harness.config(ROOT, BENCH, w["config"])
    for op in cfg["geometry"]:
        assert callable(harness.module(ROOT, "geometry", op["op"]).apply)
    mix = harness.traffic(ROOT, w["traffic"])
    assert callable(harness.module(ROOT, "drivers", mix["driver"]).run)
    for trace in (False, True):
        names = [m["name"] for m in harness.metrics_for(BENCH, cell, trace)]
        assert names
        for name in names:
            assert callable(harness.module(ROOT, "metrics", name).read)
    assert "setup_s" in [m["name"] for m in BENCH["end_to_end"]]


def test_configuration_files_are_under_the_paths():
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert harness.config(ROOT, BENCH, c["name"])["name"] == c["name"]


@pytest.mark.parametrize("lookup", [
    lambda: harness.workload(BENCH, "no.such.cell"),
    lambda: harness.config(ROOT, BENCH, "no-such-config"),
    lambda: harness.module(ROOT, "metrics", "no_such_metric"),
    lambda: geo.build(ROOT, [{"op": "no_such_op"}]),
])
def test_unknown_names_raise(lookup):
    with pytest.raises(KeyError):
        lookup()


def test_metrics_follow_their_workloads_key():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}],
             "per_layer": [{"name": "c", "workloads": ["y"]}]}
    assert [m["name"] for m in harness.metrics_for(bench, "x", False)] == ["a", "b"]
    assert [m["name"] for m in harness.metrics_for(bench, "y", False)] == ["a"]
    assert [m["name"] for m in harness.metrics_for(bench, "y", True)] == ["c"]


FAKE_DRIVER = '''
import types

def run(config, traffic, *, seed, seconds, trace, t0):
    steps = traffic["steps"]
    return types.SimpleNamespace(
        correct=True, attempted=steps, failed=0,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        checks={"answer": {"value": config["answer"], "limit": 0}},
        breakdown=None, steps=steps, n_fluid=config["fluid_nodes"],
        window_s=float(seconds), setup_s=1.5, trace=None)
'''

FAKE_GEOMETRY = '''
import numpy as np

def apply(prev, n):
    return np.ones((n, n, n), np.uint8)
'''

FAKE_METRIC = '''
def read(run):
    return 2.0 * run.steps
'''


def _snapshot(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


def test_new_files_add_a_cell_without_editing_any(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = _snapshot(root / "bench")

    (root / "bench" / "configs" / "tiny-box.json").write_text(json.dumps(
        {"name": "tiny-box", "answer": 0, "fluid_nodes": 1000}))
    (root / "bench" / "traffic" / "fake.json").write_text(json.dumps(
        {"driver": "fake_loop", "steps": 3}))
    (root / "bench" / "drivers" / "fake_loop.py").write_text(FAKE_DRIVER)
    (root / "bench" / "metrics" / "fake.doubled_steps.py").write_text(FAKE_METRIC)
    (root / "bench" / "geometry" / "open_box.py").write_text(FAKE_GEOMETRY)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-box", "source": "test",
                             "file": "bench/configs/tiny-box.json",
                             "reduced": [], "why": "test"})
    cell = {"name": "tiny.fake", "config": "tiny-box", "traffic": "fake",
            "chips": 1, "why": "test"}
    bench["workloads"].append(cell)
    bench["end_to_end"].append({"name": "fake.doubled_steps", "unit": "1",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["tiny.fake"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    result = harness.run_cell(str(root), bench, cell, seed=2**40 + 3,
                              seconds=2, trace=False, t0=0.0)
    assert result["correct"] is True and result["attempted"] == 3
    assert result["metrics"]["fake.doubled_steps"] == {"value": 6.0,
                                                       "unit": "1"}
    assert result["metrics"]["mflups"]["value"] == pytest.approx(3 * 1000 / 2 / 1e6)
    assert result["metrics"]["setup_s"]["value"] == 1.5
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "checks"]
    g = geo.build(str(root), [{"op": "open_box", "n": 3}])
    assert g.shape == (3, 3, 3) and (g == geo.FLUID).all()
    after = _snapshot(root / "bench")
    assert {k: after[k] for k in before} == before


def test_readers_that_find_nothing_are_left_out():
    bench = {"end_to_end": [{"name": "device.idle_share", "unit": "%"},
                            {"name": "setup_s", "unit": "s"}]}
    run = types.SimpleNamespace(
        correct=False, attempted=0, failed=0, device={}, checks={},
        trace=None, setup_s=3.0)
    line = harness.result_line(ROOT, bench, {"name": "c"}, run, False)
    assert line["metrics"] == {"setup_s": {"value": 3.0, "unit": "s"}}
