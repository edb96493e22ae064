"""Checkpoint/restore: roundtrip, retention, crash-resume, elastic reshard."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.ckpt.checkpoint import (list_checkpoints, restore_checkpoint,
                                   save_checkpoint)
from repro.ckpt.manager import CheckpointManager
from repro.configs.base import get_config, reduce_for_smoke
from repro.core.bp_engine import EngineConfig
from repro.train.state import init_train_state


def _small_state():
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    return cfg, init_train_state(cfg, jax.random.PRNGKey(0))


def test_roundtrip_exact(tmpdir_path):
    cfg, state = _small_state()
    save_checkpoint(tmpdir_path, state, 7, n_io_ranks=4,
                    engine_config=EngineConfig(aggregators=2, codec="blosc"))
    back, step = restore_checkpoint(tmpdir_path, state)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bfloat16_roundtrip(tmpdir_path):
    import ml_dtypes
    state = {"w": np.arange(32, dtype=np.float32).astype(ml_dtypes.bfloat16)}
    save_checkpoint(tmpdir_path, state, 1, n_io_ranks=2)
    back, _ = restore_checkpoint(tmpdir_path, state)
    np.testing.assert_array_equal(
        back["w"].view(np.uint16), state["w"].view(np.uint16))


def test_manager_retention_and_latest(tmpdir_path):
    cfg, state = _small_state()
    mgr = CheckpointManager(tmpdir_path, every=1, keep_n=2, async_write=False,
                            engine_async=True)   # AsyncBpWriter ckpt path
    for s in (1, 2, 3, 4):
        state = dict(state, step=jax.numpy.asarray(s))
        mgr.save(state, s)
    assert list_checkpoints(tmpdir_path) == [3, 4]
    restored, step = mgr.restore_latest(state)
    assert step == 4


def test_manager_skips_corrupt_checkpoint(tmpdir_path):
    cfg, state = _small_state()
    mgr = CheckpointManager(tmpdir_path, every=1, keep_n=5, async_write=False)
    mgr.save(state, 1)
    mgr.save(state, 2)
    # corrupt the newest: truncate its index
    from repro.ckpt.checkpoint import checkpoint_path
    idx = checkpoint_path(tmpdir_path, 2) / "md.idx"
    idx.write_bytes(b"")
    restored = mgr.restore_latest(state)
    assert restored is not None and restored[1] == 1


def test_async_save_overlaps(tmpdir_path):
    cfg, state = _small_state()
    mgr = CheckpointManager(tmpdir_path, every=1, keep_n=3, async_write=True)
    mgr.save(state, 1)
    mgr.save(state, 2)        # waits for 1, then writes 2 in background
    mgr.wait()
    assert list_checkpoints(tmpdir_path) == [1, 2]


def test_manager_persistent_parallel_plane_reuses_worker_pids(tmpdir_path):
    """ROADMAP item closed: parallel_io checkpoints must NOT spawn/tear
    down W processes per save — the manager keeps one WriterPlane alive,
    and two consecutive saves run on the SAME worker pids."""
    state = {"w": np.arange(256, dtype=np.float32).reshape(16, 16),
             "b": np.ones(16, dtype=np.float32)}
    with CheckpointManager(tmpdir_path, every=1, keep_n=3,
                           async_write=False, parallel_io=2,
                           n_io_ranks=4) as mgr:
        mgr.save(state, 1)
        mgr.wait()
        plane = mgr._plane
        assert plane is not None and plane.alive()
        pids = plane.pids()
        mgr.save(state, 2)
        mgr.wait()
        assert mgr._plane is plane, "manager respawned the plane"
        assert plane.pids() == pids, "saves did not reuse the worker pids"
        assert all(p.is_alive() for p, _ in plane.workers)
        assert list_checkpoints(tmpdir_path) == [1, 2]
        restored, step = mgr.restore_latest(state, parallel=2)
        assert step == 2
        np.testing.assert_array_equal(restored["w"], state["w"])
    # close() tore the plane down
    assert not plane.alive()
    assert all(not p.is_alive() for p, _ in plane.workers)


@pytest.mark.slow
def test_elastic_resharding_subprocess(tmpdir_path):
    """Save on a (2,2) mesh, restore on a (4,1) mesh — different device
    count per axis; every shard reads only its box."""
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt.checkpoint import save_checkpoint, restore_sharded

        from repro.launch.mesh import make_mesh
        mesh1 = make_mesh((2, 2), ("data", "model"))
        sh1 = NamedSharding(mesh1, P("data", "model"))
        w = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8), sh1)
        save_checkpoint(r"{tmpdir_path}", {{"w": w}}, 3, n_io_ranks=4)

        mesh2 = make_mesh((4, 1), ("data", "model"))
        sh2 = NamedSharding(mesh2, P("model", "data"))
        like = {{"w": jax.ShapeDtypeStruct((8, 8), np.float32)}}
        out, step = restore_sharded(r"{tmpdir_path}", like, {{"w": sh2}})
        assert step == 3
        np.testing.assert_array_equal(
            np.asarray(out["w"]), np.arange(64, dtype=np.float32).reshape(8, 8))
        print("ELASTIC_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=_env())
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]


def _env():
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    return env
