"""The comparison that decides ``correct``, driven through a whole run of
the harness on the CPU at a small size (the kernel interpreted): a sound run
passes; the control and each fault a one-chip cell can have fail it."""
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import control, harness  # noqa: E402

BENCH = harness.load_benchmark(ROOT)
CONFIG = dict(harness.config(ROOT, BENCH, "spheres-p07-256"),
              geometry=[{"op": "random_spheres", "box": 8, "porosity": 0.7,
                         "diameter": 4, "seed": 0},
                        {"op": "duct_wrap", "wall": 1}])
CONFIG.pop("expected")
MIX = dict(harness.traffic(ROOT, "run"), steps_per_call=2)
LIMIT = CONFIG["check"]["max_abs_df"]
SEED = 2**33 + 12345


def _run():
    driver = harness.module(ROOT, "drivers", MIX["driver"])
    return driver.run(CONFIG, MIX, seed=SEED, seconds=0.0, trace=False,
                      t0=time.perf_counter())


def test_sound_run_is_correct():
    run = _run()
    assert run.correct, run.checks
    assert run.attempted == 2 and run.failed == 0
    assert run.checks["node_set_mismatch"]["value"] == 0
    assert run.checks["max_abs_df"]["value"] < LIMIT / 10


def _unchanged(step):
    return lambda self, f, tab: f


def _half_the_tiles(step):
    def broken(self, f, tab):
        out = step(self, f, tab)
        half = (f.shape[0] - 1) // 2
        return out.at[half:-1].set(f[half:-1])
    return broken


def _answer_altered(step):
    def broken(self, f, tab):
        return step(self, f, tab).at[0, 0].multiply(1.01)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_the_tiles,
                                   _answer_altered])
def test_a_broken_step_is_not_correct(monkeypatch, fault):
    from repro.core.backends import FusedBackend

    monkeypatch.setattr(FusedBackend, "step", fault(FusedBackend.step))
    run = _run()
    assert not run.correct
    assert run.checks["max_abs_df"]["value"] > LIMIT


def _readings(runs, calls=1):
    return {(kind, seed): run for kind, seed, run in
            control.readings(CONFIG, MIX, runs, calls)}


def test_program_readings_lie_below_the_limit():
    runs = _readings([("program", SEED), ("program", 5)])
    assert list(runs) == [("program", SEED), ("program", 5)]
    for run in runs.values():
        assert run.correct, run.checks
        assert run.attempted == 2
        assert run.checks["max_abs_df"]["value"] < LIMIT / 10


def test_control_readings_lie_above_the_limit():
    # the bfloat16 reference in the program's place, judged by the run's own
    # comparison and printed as the harness's own result line
    runs = _readings([("control", s) for s in (1, 2, 3)])
    cell = harness.workload(BENCH, "spheres256.run")
    for run in runs.values():
        line = harness.result_line(ROOT, BENCH, cell, run, False)
        assert line["correct"] is False and line["failed"] == 0
        assert line["checks"]["max_abs_df"]["value"] > LIMIT
        assert line["checks"]["node_set_mismatch"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_planted_faults_are_not_correct(fault):
    (run,) = _readings([(fault, SEED)], calls=2).values()
    assert not run.correct
    assert run.attempted == 4
    assert run.checks["max_abs_df"]["value"] > LIMIT


def test_float32_reference_in_the_programs_place_is_correct():
    # the stand-in machinery alone changes nothing: the reference in the
    # configuration's own precision reads what it is judged by, to round-off
    # (the initial flow is evaluated on the tiles and on the box)
    g = harness.module(ROOT, "drivers", "lbm_run")
    built = g.build(CONFIG, MIX)
    stand_in = control.ReferenceInPlace(built[0], CONFIG, CONFIG["dtype"])
    run = g.run(CONFIG, MIX, seed=SEED, seconds=0.0, trace=False,
                t0=time.perf_counter(), built=built, stand_in=stand_in,
                calls=1)
    assert run.correct and run.checks["max_abs_df"]["value"] < 1e-6


def test_node_set_mismatch_counts_missing_extra_and_repeated_nodes():
    lbm_run = harness.module(ROOT, "drivers", "lbm_run")
    g = np.zeros((2, 2, 2), np.uint8)
    g[0, 0, 0] = g[1, 1, 1] = 1
    both = np.array([[0, 0, 0], [1, 1, 1]])
    assert lbm_run.node_set_mismatch(g, both) == 0
    assert lbm_run.node_set_mismatch(g, both[:1]) == 1
    assert lbm_run.node_set_mismatch(g, np.array([[0, 0, 0], [1, 1, 1],
                                                  [0, 1, 0]])) == 1
    assert lbm_run.node_set_mismatch(g, np.array([[0, 0, 0], [0, 0, 0],
                                                  [1, 1, 1]])) == 1
