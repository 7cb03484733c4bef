#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the accelerator.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Loads, warms up, measures for ``--seconds``, checks the state the window
produced against the dense reference, and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device``, ``breakdown`` (traced runs) and ``checks`` (each number compared
with its limit, also the last lines of standard error).  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.  JAX's compilation cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, CHECKOUT)
    from bench import harness

    bench = harness.load_benchmark(CHECKOUT)
    cell = harness.workload(bench, args.workload)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no logs under /tmp
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(CHECKOUT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2

    result = harness.run_cell(CHECKOUT, bench, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              t0=T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
