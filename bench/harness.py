"""Finds a cell's parts by name and assembles its result line.

``BENCHMARK.json`` names each cell's configuration, traffic mix and metrics.
A configuration is the file its entry names; a traffic mix is
``bench/traffic/<mix>.json``; the mix names its driver,
``bench/drivers/<driver>.py``; each metric is read by
``bench/metrics/<metric>.py``.  A new cell, configuration, mix or metric is
new files and entries: nothing here names one.

Every lookup takes the checkout's root, the directory that holds
``BENCHMARK.json`` and ``bench/``.  A driver exposes ``run(config, traffic, *, seed, seconds, trace, t0)`` and
returns an object with ``correct``, ``attempted``, ``failed``, ``device``,
``checks`` ({name: {"value", "limit"}}) and, when traced, ``breakdown``; a
metric reader exposes ``read(run) -> float | None`` (``None``: nothing to
read, and the metric is left out of the line).
"""
from __future__ import annotations

import importlib.util
import json
import os


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(root: str, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(root, c["file"]))
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(root: str, name: str) -> dict:
    return load_json(os.path.join(root, "bench", "traffic", f"{name}.json"))


def module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The end-to-end (untraced) or per-layer (traced) metrics this cell
    reports: those without a ``workloads`` key, and those that list it."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def result_line(root: str, bench: dict, cell: dict, run, trace: bool) -> dict:
    metrics = {}
    for m in metrics_for(bench, cell["name"], trace):
        value = module(root, "metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics,
           "device": run.device}
    if trace:
        out["breakdown"] = run.breakdown
    out["checks"] = run.checks
    return out


def run_cell(root: str, bench: dict, cell: dict, *, seed: int,
             seconds: float, trace: bool, t0: float) -> dict:
    """Run one cell once; returns its result line as a dict."""
    cfg = config(root, bench, cell["config"])
    mix = traffic(root, cell["traffic"])
    run = module(root, "drivers", mix["driver"]).run(
        cfg, mix, seed=seed, seconds=seconds, trace=trace, t0=t0)
    return result_line(root, bench, cell, run, trace)
