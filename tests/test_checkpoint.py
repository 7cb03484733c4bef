"""Checkpoint store: atomic commit, async save, digests, elastic restore,
restart-exactness with the data pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import COMMITTED, CheckpointStore


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (8, 16)),
            "nested": {"b": jnp.arange(10, dtype=jnp.int32),
                       "c": jnp.ones((3, 3, 3), jnp.bfloat16)}}


def test_save_restore_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path))
    t = _tree()
    store.save(7, {"params": t}, extra={"step": 7, "data": {"step": 7}})
    assert store.latest() == 7
    out, extra = store.restore(7, {"params": jax.tree.map(np.asarray, t)})
    assert extra["step"] == 7
    for a, b in zip(jax.tree.leaves(out["params"]), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_then_restore(tmp_path):
    store = CheckpointStore(str(tmp_path))
    t = _tree(1)
    store.save_async(3, {"params": t}, extra={"step": 3})
    store.wait()
    assert store.latest() == 3
    assert store.verify(3)


def test_torn_checkpoint_ignored(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save(5, {"params": _tree()}, extra={})
    # simulate a torn save at step 9 (no COMMITTED marker)
    torn = tmp_path / "step_000000009"
    torn.mkdir()
    (torn / "manifest.json").write_text("{}")
    assert store.latest() == 5


def test_gc_keeps_newest(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        store.save(s, {"params": {"x": np.ones(4)}}, extra={})
    names = sorted(os.listdir(tmp_path))
    assert names == ["step_000000003", "step_000000004"]


def test_elastic_restore_resharding(tmp_path):
    """A checkpoint saved unsharded restores under a DIFFERENT sharding
    (single-device here: NamedSharding over a 1-device mesh)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    store = CheckpointStore(str(tmp_path))
    t = {"w": np.arange(64, dtype=np.float32).reshape(8, 8)}
    store.save(1, {"params": t}, extra={})
    mesh = jax.make_mesh((1,), ("data",))
    sh = {"params": {"w": NamedSharding(mesh, P("data", None))}}
    out, _ = store.restore(1, {"params": t}, shardings=sh)
    np.testing.assert_array_equal(np.asarray(out["params"]["w"]), t["w"])


def test_lbm_state_dtype_roundtrip(tmp_path):
    """LBM session payloads survive the raw-byte shard format exactly:
    float64 populations, int32 index tables, uint8 geometry — dtype,
    shape and every bit preserved."""
    store = CheckpointStore(str(tmp_path))
    rng = np.random.default_rng(3)
    tree = {
        "f": rng.standard_normal((19, 7, 64)),             # float64
        "gather_idx": rng.integers(0, 19 * 7 * 64,
                                   (19, 7, 64)).astype(np.int32),
        "geometry": rng.integers(0, 4, (12, 12, 12)).astype(np.uint8),
    }
    store.save(2, {"session": tree}, extra={"sid": 0})
    assert store.verify(2)
    out, _ = store.restore(2, {"session": tree})
    for key, arr in tree.items():
        got = out["session"][key]
        assert got.dtype == arr.dtype, key
        np.testing.assert_array_equal(got, arr, err_msg=key)


def test_restore_trees_from_manifest_alone(tmp_path):
    """restore_trees rebuilds nested dict trees purely from the manifest
    (no caller-side tree_likes) — the session restore path's API."""
    store = CheckpointStore(str(tmp_path))
    tree = {"f": np.arange(12.0).reshape(3, 4),
            "nested": {"idx": np.arange(5, dtype=np.int32)}}
    store.save(1, {"s0": tree, "geometries": {"abc": np.ones(3, np.uint8)}},
               extra={"k": 1})
    out, extra = store.restore_trees(1)
    assert extra == {"k": 1}
    np.testing.assert_array_equal(out["s0"]["f"], tree["f"])
    np.testing.assert_array_equal(out["s0"]["nested"]["idx"],
                                  tree["nested"]["idx"])
    assert out["geometries"]["abc"].dtype == np.uint8


@pytest.mark.usefixtures("x64")
def test_torn_recovery_through_session_restore(tmp_path):
    """The new session restore path (repro.sim.service) recovers from a
    torn save: a checkpoint directory missing COMMITTED is skipped and the
    previous good step is restored bit-exactly."""
    from repro.core.engine import LBMConfig
    from repro.sim.service import SimService

    cfg = LBMConfig(layout_scheme="paper", dtype="float64",
                    periodic=(True, True, True), backend="gather")
    g = np.ones((8, 8, 8), np.uint8)
    root = str(tmp_path / "sessions")
    svc = SimService(slots=1, checkpoint_root=root)
    svc.submit(g, cfg, steps=5)
    svc.step(3)
    svc.checkpoint()
    good = np.asarray(svc.live_sessions()[0][1])
    svc.step(1)
    torn = svc.checkpoint()
    os.remove(os.path.join(torn, COMMITTED))

    svc2 = SimService.restore(root, slots=1)
    sess, f = svc2.live_sessions()[0]
    assert sess.steps_done == 3                 # the good step, not 4
    np.testing.assert_array_equal(f, good)
    assert f.dtype == np.float64
    finished = svc2.run()
    assert finished[0].result["steps"] == 5
    assert finished[0].result["mass_drift"] < 1e-12


def test_restart_reproduces_data_stream(tmp_path):
    from repro.data.tokens import DataConfig, TokenPipeline
    cfg = DataConfig(vocab_size=97, seq_len=32, global_batch=4, seed=5)
    p1 = TokenPipeline(cfg)
    for _ in range(3):
        p1.next()
    state = p1.state()
    expected = p1.next()
    p2 = TokenPipeline(cfg)
    p2.restore(state)
    got = p2.next()
    np.testing.assert_array_equal(got["tokens"], expected["tokens"])
