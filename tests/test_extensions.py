"""Beyond-paper extensions: microbatch accumulation, SST streaming,
pod-ZeRO-1 specs, straggler absorption."""
import time

import jax
import numpy as np
import pytest

from repro.configs.base import get_config, reduce_for_smoke
from repro.optim.adamw import AdamWConfig
from repro.train.state import init_train_state
from repro.train.step import make_train_step


def test_microbatch_equals_full_batch():
    cfg = reduce_for_smoke(get_config("qwen1.5-0.5b"))
    s1 = init_train_state(cfg, jax.random.PRNGKey(0))
    s2 = jax.tree_util.tree_map(lambda x: x.copy(), s1)
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32),
             "labels": rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
    hp = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=10)
    kw = dict(q_chunk=16, kv_chunk=16, ssd_chunk=16)
    o1, m1 = jax.jit(make_train_step(cfg, hp, **kw))(s1, batch)
    o2, m2 = jax.jit(make_train_step(cfg, hp, microbatches=4, **kw))(s2, batch)
    for a, b in zip(jax.tree_util.tree_leaves(o1["params"]),
                    jax.tree_util.tree_leaves(o2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=3e-4)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3


def test_sst_streaming_roundtrip():
    from repro.core.sst_engine import SstStream, attach_consumer
    stream = SstStream(queue_depth=2)
    seen = {}
    t = attach_consumer(stream, lambda step, data: seen.update({step: data}))
    for s in range(3):
        stream.begin_step(s)
        stream.put("n", np.full(4, s, np.float32), global_shape=(8,),
                   offset=(0,))
        stream.put("n", np.full(4, s + 10, np.float32), global_shape=(8,),
                   offset=(4,))
        stream.end_step()
    stream.close()
    t.join(timeout=5)
    assert sorted(seen) == [0, 1, 2]
    np.testing.assert_array_equal(
        seen[2]["n"], np.concatenate([np.full(4, 2.0), np.full(4, 12.0)]))


def test_opt_moments_shard_over_pod():
    from repro.train.state import train_state_shardings
    mesh = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    cfg = get_config("qwen3-4b")
    sh = train_state_shardings(cfg, mesh)
    m_spec = sh["opt"]["m"]["stack"]["layers"]["ffn"]["gate"]["w"].spec
    p_spec = sh["params"]["stack"]["layers"]["ffn"]["gate"]["w"].spec
    flat_m = [a for e in m_spec if e for a in
              (e if isinstance(e, tuple) else (e,))]
    flat_p = [a for e in p_spec if e for a in
              (e if isinstance(e, tuple) else (e,))]
    assert "pod" in flat_m and "pod" not in flat_p


def test_straggler_ost_absorbed_by_pool():
    """Work-stealing writer pool: a slow OST delays its own stripe stream,
    not the whole step (aggregate wall < serialized sum)."""
    import pathlib
    import tempfile
    from repro.core.bp_engine import BpWriter, EngineConfig
    from repro.core.striping import StripeConfig
    import shutil
    d = pathlib.Path(tempfile.mkdtemp())
    try:
        import repro.core.bp_engine as BE
        from repro.core.striping import OstPool
        # 4 aggregators, OST 0 is slow; pool workers absorb
        cfg = EngineConfig(aggregators=4, workers=4,
                           stripe=StripeConfig(2, 1 << 16), n_osts=4)
        w = BpWriter(d / "s.bp4", 8, cfg)
        w.subfiles._files[0].pool.slow_osts[0] = 0.2    # 200 ms/write on ost0
        t0 = time.perf_counter()
        w.begin_step(0)
        rng = np.random.default_rng(0)
        for r in range(8):
            w.put("x", rng.normal(size=(1 << 15,)).astype(np.float32),
                  global_shape=(8 << 15,), offset=(r << 15,), rank=r)
        w.end_step()
        w.close()
        wall = time.perf_counter() - t0
        # the slow aggregator pays its ~200ms writes while the others
        # proceed in parallel: absorbed wall measures ~0.75s. Fully
        # serializing every stripe behind the slow OST would cost
        # >= 8 x 2 x 200ms = 3.2s — the threshold sits under that with
        # ~2s of headroom for scheduler stalls on noisy shared machines.
        assert wall < 3.0, wall
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_darshan_parser_dump(tmpdir_path):
    from repro.core.darshan import MONITOR, open_file
    MONITOR.reset()
    with open_file(tmpdir_path / "x.bin", "wb", rank=1) as f:
        f.write(b"abc" * 100)
    txt = MONITOR.parser_dump(n_procs=4)
    assert "total_POSIX_WRITES\t1.000000" in txt
    assert "x.bin" in txt and "hist\t" in txt


def test_distributed_helpers():
    from repro.launch.distributed import initialize, io_rank_range
    info = initialize()                      # single-process no-op path
    assert info["num_processes"] == 1 and info["global_devices"] >= 1
    ranges = [list(io_rank_range(64, p, 4)) for p in range(4)]
    flat = [r for rr in ranges for r in rr]
    assert flat == list(range(64))           # partition, no overlap
