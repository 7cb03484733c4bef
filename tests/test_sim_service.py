"""SimService + EngineRegistry: fixed-slot multi-tenant serving, engine
sharing, probe readouts, checkpoint / torn-checkpoint restore."""
import os

import numpy as np
import pytest

from repro.checkpoint.store import COMMITTED
from repro.core import collision as C
from repro.core.engine import LBMConfig, SparseTiledLBM
from repro.sim.registry import (EngineRegistry, config_from_dict,
                                config_signature, config_to_dict,
                                geometry_fingerprint)
from repro.sim.service import SimService, probe_indices


pytestmark = pytest.mark.usefixtures("x64")


def _box(n=8):
    return np.ones((n, n, n), np.uint8)


def _channel():
    g = np.ones((8, 8, 8), np.uint8)
    g[:, 0, :] = 0
    g[:, -1, :] = 0
    return g


CFG = LBMConfig(layout_scheme="paper", dtype="float64",
                periodic=(True, True, True), backend="gather")
CFG_FORCE = LBMConfig(layout_scheme="paper", dtype="float64",
                      periodic=(True, False, True),
                      force=(1e-5, 0.0, 0.0), backend="gather")


# ---------------------------------------------------------------- registry
def test_registry_shares_engine():
    reg = EngineRegistry()
    e1 = reg.get(_box(), CFG)
    e2 = reg.get(_box().copy(), CFG)          # same content, new array
    assert e1 is e2 and e1.engine is e2.engine
    assert reg.compiled_count == 1
    # get() is a pure lookup — hits are recorded only by seating consumers
    assert e1.hits == 0


def test_shared_registry_isolates_service_state():
    """Two services over ONE registry share the compiled engine but never
    flow state: stepping service A leaves B's seated tenant untouched."""
    reg = EngineRegistry()
    a = SimService(slots=1, registry=reg)
    b = SimService(slots=1, registry=reg)
    a.submit(_box(), CFG, steps=50)
    b.submit(_box(), CFG, steps=50)
    a.step(1)
    b.step(1)                                  # both seated now
    assert reg.compiled_count == 1             # engine genuinely shared
    key = next(iter(a.groups))
    assert a.groups[key].entry is b.groups[key].entry
    assert a.groups[key].ensemble is not b.groups[key].ensemble
    fb0 = np.asarray(b.groups[key].ensemble.replica_canonical(0))
    a.step(3)                                  # advance A only
    np.testing.assert_array_equal(
        np.asarray(b.groups[key].ensemble.replica_canonical(0)), fb0)


def test_queue_poll_does_not_inflate_hits():
    """A session waiting behind a full group neither re-hashes its
    geometry per poll (key cached on the session) nor inflates the
    entry's hit count; it contributes exactly one hit when seated."""
    svc = SimService(slots=1)
    svc.submit(_box(), CFG, steps=3)
    svc.submit(_box(), CFG, steps=1)           # queued behind slot 0
    svc.step(2)                                # sid 1 polled twice, unseated
    (entry,) = svc.registry._entries.values()
    assert entry.hits == 1
    assert svc.queue[0].engine_key is not None  # cached at first poll
    svc.run()
    assert entry.hits == 2                     # exactly one hit per session


def test_registry_distinguishes_config_and_geometry():
    reg = EngineRegistry()
    reg.get(_box(), CFG)
    reg.get(_box(), LBMConfig(layout_scheme="paper", dtype="float64",
                              periodic=(True, True, True),
                              backend="gather", split_stream=True))
    reg.get(_channel(), CFG)
    assert reg.compiled_count == 3
    stats = reg.stats()
    assert stats["compiled_engines"] == 3 and stats["hits"] == 0


def test_config_signature_roundtrip():
    """config_to_dict/from_dict is lossless (signature-stable), including
    nested BoundarySpec/CollisionConfig and the force tuple."""
    from repro.core.boundary import BoundarySpec
    from repro.core.tiling import INLET

    cfg = LBMConfig(
        collision=C.CollisionConfig(model="lbmrt", tau=0.7),
        boundaries=((INLET, BoundarySpec("velocity", (0, 0, 1),
                                         velocity=(0, 0, 0.02))),),
        force=(1e-5, 0.0, 0.0), split_stream=True, tile_order="morton")
    cfg2 = config_from_dict(config_to_dict(cfg))
    assert cfg2 == cfg
    assert config_signature(cfg2) == config_signature(cfg)
    assert config_signature(cfg) != config_signature(CFG)


def test_geometry_fingerprint_content_addressed():
    g = _box()
    assert geometry_fingerprint(g) == geometry_fingerprint(g.copy())
    g2 = g.copy()
    g2[3, 3, 3] = 0
    assert geometry_fingerprint(g) != geometry_fingerprint(g2)


# ----------------------------------------------------------------- service
def test_service_end_to_end_slot_refill():
    """3 sessions, 2 slots, one geometry: the third session waits in the
    queue and is seated when the shortest budget finishes; every session
    conserves mass and runs exactly its budget."""
    svc = SimService(slots=2)
    sids = [svc.submit(_box(), CFG, steps=s) for s in (3, 5, 4)]
    finished = svc.run()
    assert sorted(s.sid for s in finished) == sorted(sids)
    assert svc.registry.compiled_count == 1
    for sess in finished:
        r = sess.result
        assert r["steps"] == sess.max_steps
        assert r["mass_drift"] < 1e-12
    # collect() finds results by sid; unknown sid -> None
    assert svc.collect(sids[0])["sid"] == sids[0]
    assert svc.collect(999) is None


def test_submit_copies_geometry():
    """In-place mutation of the caller's array after submit must not
    corrupt the session's key or checkpointed geometry."""
    svc = SimService(slots=1)
    g = _box()
    svc.submit(g, CFG, steps=2)
    g[:] = 0                                   # caller trashes their buffer
    finished = svc.run()
    assert finished[0].result["mass_drift"] < 1e-12
    assert svc.registry.compiled_count == 1


def test_release_idle_groups():
    """Idle groups (device state) can be released; the compiled engine
    stays registered, so a re-submit reuses it without re-tiling."""
    svc = SimService(slots=1)
    svc.submit(_box(), CFG, steps=2)
    svc.run()
    assert len(svc.groups) == 1
    assert svc.release_idle() == 1
    assert not svc.groups
    assert svc.registry.compiled_count == 1    # engine survives
    eng = next(iter(svc.registry._entries.values())).engine
    svc.submit(_box(), CFG, steps=2)
    svc.run()
    assert next(iter(svc.groups.values())).entry.engine is eng
    # a group with a queued session for its key is NOT idle
    svc.submit(_box(), CFG, steps=2)
    svc.submit(_box(), CFG, steps=2)           # second waits in queue
    svc.step(1)
    assert svc.release_idle() == 0


def test_zero_step_budget_rejected():
    svc = SimService(slots=1)
    with pytest.raises(ValueError, match="budget"):
        svc.submit(_box(), CFG, steps=0)


def test_run_warns_on_max_steps_exhaustion():
    svc = SimService(slots=1)
    svc.submit(_box(), CFG, steps=10)
    with pytest.warns(RuntimeWarning, match="unfinished"):
        finished = svc.run(max_steps=3)
    assert not finished
    assert svc.run()[0].result["steps"] == 10   # still resumable


def test_service_two_geometries_probes():
    svc = SimService(slots=2)
    probe = ((4, 4, 4),)
    sid_a = svc.submit(_box(), CFG, steps=3, probes=probe)
    sid_b = svc.submit(_channel(), CFG_FORCE, steps=6, probes=probe)
    svc.run()
    assert svc.registry.compiled_count == 2
    ra, rb = svc.collect(sid_a), svc.collect(sid_b)
    assert ra["probes"][0]["point"] == [4, 4, 4]
    assert ra["probes"][0]["rho"] == pytest.approx(1.0, abs=1e-9)
    # the forced channel accelerates from rest: probe sees downstream flow
    assert rb["probes"][0]["u"][0] > 0
    assert rb["mean_speed"] > 0


def test_collect_fields_dense_readout():
    """collect_fields=True attaches the dense macroscopic grids with the
    same conventions as SparseTiledLBM.fields_dense: solid nodes in kept
    tiles read rho0 / zero u, only dropped tiles read the NaN fill."""
    svc = SimService(slots=1)
    sid = svc.submit(_channel(), CFG_FORCE, steps=4, collect_fields=True)
    svc.run()
    r = svc.collect(sid)
    assert r["rho_dense"].shape == (8, 8, 8)
    assert r["u_dense"].shape == (3, 8, 8, 8)
    assert (r["rho_dense"][:, 0, :] == 1.0).all()           # wall -> rho0
    assert (r["u_dense"][:, :, 0, :] == 0).all()
    assert np.nanmax(np.abs(r["u_dense"])) > 0              # flow started


def test_probe_validation():
    svc = SimService(slots=1)
    eng = SparseTiledLBM(_channel(), CFG_FORCE)
    with pytest.raises(ValueError, match="out of grid"):
        probe_indices(eng.tiling, ((99, 0, 0),))
    with pytest.raises(ValueError, match="probes must be"):
        probe_indices(eng.tiling, ((1, 2),))
    # a probe into a wall node is allowed (reads rho0/0) but a probe into
    # a DROPPED tile is rejected at submit time
    g = _box(8)
    g[:4] = 0                                   # empty half -> dropped tiles
    with pytest.raises(ValueError, match="empty"):
        svc.submit(g, CFG, steps=1, probes=((0, 4, 4),))
    # padded geometries: bounds are the ORIGINAL extent, not the padded
    # tile multiple — a probe into the solid padding ring must be rejected
    eng10 = SparseTiledLBM(np.ones((10, 10, 10), np.uint8), CFG)
    assert eng10.tiling.shape == (12, 12, 12)
    probe_indices(eng10.tiling, ((9, 9, 9),))   # last real node: fine
    with pytest.raises(ValueError, match="out of grid"):
        probe_indices(eng10.tiling, ((10, 10, 10),))


def test_checkpoint_restore_resumes_exactly(tmp_path):
    """Kill mid-flight, restore, finish: results identical (gather backend
    => bitwise state carry-over through the canonical checkpoint)."""
    root = str(tmp_path / "ck")
    svc = SimService(slots=2, checkpoint_root=root)
    svc.submit(_box(), CFG, steps=8)
    svc.submit(_channel(), CFG_FORCE, steps=10, probes=((4, 4, 4),))
    ref = SimService(slots=2)
    ref.submit(_box(), CFG, steps=8)
    ref.submit(_channel(), CFG_FORCE, steps=10, probes=((4, 4, 4),))

    svc.step(4)
    svc.checkpoint()
    del svc                                     # "kill" the server

    svc2 = SimService.restore(root, slots=2)
    finished = svc2.run()
    ref_finished = ref.run()
    assert len(finished) == len(ref_finished) == 2
    for sess, rsess in zip(sorted(finished, key=lambda s: s.sid),
                           sorted(ref_finished, key=lambda s: s.sid)):
        assert sess.result["steps"] == rsess.result["steps"]
        assert sess.result["mass"] == rsess.result["mass"]       # bitwise
        assert sess.result["mass_drift"] < 1e-9   # forced channel: 1e-9
        if "probes" in sess.result:
            assert sess.result["probes"] == rsess.result["probes"]


def test_checkpoint_preserves_queue(tmp_path):
    """A queued-but-never-seated session survives checkpoint/restore."""
    root = str(tmp_path / "ck")
    svc = SimService(slots=1, checkpoint_root=root)
    svc.submit(_box(), CFG, steps=4)
    svc.submit(_box(), CFG, steps=2)            # waits in queue (1 slot)
    svc.step(1)
    assert len(svc.queue) == 1
    svc.checkpoint()
    svc2 = SimService.restore(root, slots=1)
    finished = svc2.run()
    assert sorted(s.sid for s in finished) == [0, 1]
    assert all(s.result["mass_drift"] < 1e-12 for s in finished)


def test_checkpoint_dedups_geometry(tmp_path):
    """N sessions on one geometry store it ONCE per save (keyed by the
    registry's content fingerprint), not N times."""
    import json

    root = str(tmp_path / "ck")
    svc = SimService(slots=2, checkpoint_root=root)
    svc.submit(_box(), CFG, steps=5)
    svc.submit(_box(), CFG, steps=5)
    svc.submit(_channel(), CFG_FORCE, steps=5)
    svc.step(1)
    path = svc.checkpoint()
    with open(os.path.join(path, "manifest.json")) as fh:
        manifest = json.load(fh)
    assert len(manifest["trees"]["geometries"]) == 2    # 3 sessions, 2 geoms
    svc2 = SimService.restore(root, slots=2)
    for sess in svc2.queue:                 # restored key skips re-hashing
        assert sess.engine_key is not None
    finished = svc2.run()
    assert len(finished) == 3
    assert all(s.result["mass_drift"] < 1e-9 for s in finished)


def test_finished_results_survive_restart(tmp_path):
    """A completed-but-uncollected result (scalars AND dense fields) is
    checkpointed and collectable after restore."""
    root = str(tmp_path / "ck")
    svc = SimService(slots=2, checkpoint_root=root)
    sid_a = svc.submit(_box(), CFG, steps=2, probes=((4, 4, 4),),
                       collect_fields=True)
    sid_b = svc.submit(_box(), CFG, steps=6)
    svc.step(3)                                 # A finished, B mid-flight
    assert svc.collect(sid_a) is not None
    svc.checkpoint()
    ref = svc.collect(sid_a)
    del svc

    svc2 = SimService.restore(root, slots=2)
    got = svc2.collect(sid_a)
    assert got is not None
    assert got["mass"] == ref["mass"] and got["probes"] == ref["probes"]
    np.testing.assert_array_equal(got["rho_dense"], ref["rho_dense"])
    svc2.run()
    assert svc2.collect(sid_b)["steps"] == 6
    assert sorted(s.sid for s in svc2.finished) == [sid_a, sid_b]


def test_torn_checkpoint_falls_back(tmp_path):
    """A save without COMMITTED is ignored: restore resumes from the
    previous good checkpoint (the session restore path end to end)."""
    root = str(tmp_path / "ck")
    svc = SimService(slots=1, checkpoint_root=root)
    sid = svc.submit(_box(), CFG, steps=6)
    svc.step(2)
    svc.checkpoint()                            # good save @ ckpt step 0
    svc.step(2)
    path = svc.checkpoint()                     # newer save @ ckpt step 1
    os.remove(os.path.join(path, COMMITTED))    # tear it
    svc2 = SimService.restore(root, slots=1)
    (sess, f) = svc2.live_sessions()[0]
    assert sess.sid == sid and sess.steps_done == 2   # NOT 4
    finished = svc2.run()
    assert finished[0].result["steps"] == 6
    assert finished[0].result["mass_drift"] < 1e-12


def test_reused_root_continues_numbering(tmp_path):
    """A fresh service over a non-empty checkpoint root numbers its saves
    ABOVE the existing ones — restarting at 0 would let the keep-newest
    gc delete the new run's saves and leave restore() on the stale run."""
    root = str(tmp_path / "ck")
    svc1 = SimService(slots=1, checkpoint_root=root, keep=2)
    svc1.submit(_box(), CFG, steps=6)
    for _ in range(3):
        svc1.step(1)
        svc1.checkpoint()                   # saves 0, 1, 2 (gc keeps 1, 2)
    del svc1

    svc2 = SimService(slots=1, checkpoint_root=root, keep=2)
    svc2.submit(_box(), CFG, steps=4)
    svc2.step(1)
    svc2.checkpoint()                       # must be save 3, not save 0
    svc3 = SimService.restore(root, slots=1)
    (sess, _) = svc3.live_sessions()[0]
    assert sess.max_steps == 4 and sess.steps_done == 1   # the NEW run


def test_restore_without_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        SimService.restore(str(tmp_path / "empty"))
