"""sim-serve smoke (CI): multi-tenant serving with checkpointed restart.

Submits 3 sessions over 2 geometries into a 2-slot-per-group service,
steps, checkpoints, kills the service, restores, and runs to completion.
Asserts:

* the registry compiled exactly 2 engines (3 sessions, 2 distinct
  (geometry, config) keys) — before AND after the restart,
* every session ran exactly its step budget across the kill/restore,
* per-session mass conservation to 1e-12 (closed/periodic geometries,
  float64),
* the slot-refill path ran (3 sessions through 2 slots in one group),
* the obs registry saw every finish and its per-session
  ``lbm.mass.drift`` gauges agree with the results (drift < 1e-12).

Run:  PYTHONPATH=src python tests/progs/sim_serve_smoke.py [metrics.jsonl]
(the optional argument exports the metric registry as JSONL, for CI
artifact upload)
"""
import os
import sys
import tempfile

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.core.engine import LBMConfig  # noqa: E402
from repro.sim.service import SimService  # noqa: E402


def main():
    obs.enable(trace=True)
    box = np.ones((8, 8, 8), np.uint8)           # periodic all-fluid box
    channel = np.ones((8, 8, 8), np.uint8)       # walled forced channel
    channel[:, 0, :] = 0
    channel[:, -1, :] = 0
    cfg_box = LBMConfig(layout_scheme="paper", dtype="float64",
                        periodic=(True, True, True), backend="gather")
    cfg_chan = LBMConfig(layout_scheme="paper", dtype="float64",
                         periodic=(True, False, True),
                         force=(1e-5, 0.0, 0.0), backend="gather",
                         split_stream=True)

    with tempfile.TemporaryDirectory() as tmp:
        root = os.path.join(tmp, "sessions")
        svc = SimService(slots=2, checkpoint_root=root)
        sids = [
            svc.submit(box, cfg_box, steps=6, probes=((4, 4, 4),)),
            svc.submit(box, cfg_box, steps=9),
            svc.submit(channel, cfg_chan, steps=7),
        ]
        svc.step(4)
        assert svc.registry.compiled_count == 2, svc.registry.stats()
        svc.checkpoint()
        del svc                                   # kill the server

        svc2 = SimService.restore(root, slots=2)
        finished = svc2.run()
        assert svc2.registry.compiled_count == 2, svc2.registry.stats()
        assert sorted(s.sid for s in finished) == sorted(sids)
        for sess in sorted(finished, key=lambda s: s.sid):
            r = sess.result
            assert r["steps"] == sess.max_steps, r
            assert r["mass_drift"] < 1e-12, r
            print(f"sid={r['sid']} steps={r['steps']} "
                  f"mass={r['mass']:.12f} drift={r['mass_drift']:.2e}")
        probed = svc2.collect(sids[0])
        assert probed["probes"][0]["rho"] > 0
        stats = svc2.registry.stats()
        assert stats["compiled_engines"] == 2

        # --- obs: counters and the per-session mass-drift gauges must
        # agree with the collected results (registry enabled up top)
        reg = obs.get_metrics()
        assert reg.value("sim.session.finished_total") == 3, reg.snapshot()
        drifts = reg.values("lbm.mass.drift")
        assert len(drifts) == 3, drifts
        worst = max(drifts.values())
        assert worst < 1e-12, f"mass-drift gauge regressed: {drifts}"
        assert reg.value("ckpt.save_total") >= 1
        assert reg.value("ckpt.restore_total") >= 1
        assert obs.get_tracer().find("sim.service.step"), "no serving spans"
        if len(sys.argv) > 1:
            print(f"metrics -> {reg.write_jsonl(sys.argv[1])}")
    print("sim_serve_smoke OK: 3 sessions, 2 geometries, 2 compiled "
          "engines, mass conserved across checkpointed restart "
          f"(max drift gauge {worst:.2e})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
