"""The program's spans on the profiler's clock: every `TRACER.span(op)`
shows up as `jbp.<op>` on the `/host:CPU` plane of a `jax.profiler`
trace, whether the DXT ring is on or off, and the same exit feeds the
ring and the metrics histogram."""
import collections
import glob
import os
import pathlib
import subprocess
import sys
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData
from jax.sharding import SingleDeviceSharding

from repro.ckpt.manager import CheckpointManager
from repro.configs.bit1 import cpu_config
from repro.core.bp_engine import EngineConfig
from repro.core import dxt
from repro.core.dxt import POSIX_OPS, SPAN_OPS, TRACE_PREFIX, DxtTracer
from repro.core.metrics import METRICS
from repro.pic.simulation import (init_sim, open_diagnostic_series,
                                  run_with_diagnostics)


def host_spans(trace_dir) -> collections.Counter:
    """Count of each `jbp.*` event on the trace's `/host:CPU` plane."""
    files = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    assert files, f"no trace under {trace_dir}"
    out = collections.Counter()
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                out.update(e.name for e in line.events
                           if e.name.startswith(TRACE_PREFIX))
    return out


def test_span_ops_never_reuse_a_posix_op():
    assert not set(SPAN_OPS) & set(POSIX_OPS)
    assert len(set(SPAN_OPS)) == len(SPAN_OPS)


def test_pic_loop_and_write_plane_spans(tmpdir_path):
    cfg = cpu_config(256)
    state = init_sim(cfg, jax.random.PRNGKey(0))
    # compile outside the trace
    state = run_with_diagnostics(state, cfg, None, n_chunks=1,
                                 steps_per_chunk=1)
    series = open_diagnostic_series(tmpdir_path / "diag.bp4", n_io_ranks=4,
                                    parallel_io=2)
    try:
        with jax.profiler.trace(str(tmpdir_path / "trace")):
            run_with_diagnostics(state, cfg, series, n_chunks=2,
                                 steps_per_chunk=1, dump_every=1,
                                 n_io_ranks=4)
    finally:
        series.close()
    got = host_spans(tmpdir_path / "trace")
    for op in ("pic_chunk", "diagnostics", "dump_copy", "series_flush"):
        assert got[f"jbp.{op}"] == 2, (op, got)
    assert got["jbp.snapshot"] == 2, got
    assert got["jbp.prepare"] >= 2 and got["jbp.commit"] >= 2, got
    assert got["jbp.series_drain"] == 1, got


def test_checkpoint_spans(tmpdir_path):
    dev = jax.devices()[0]
    state = {"a": jnp.arange(4096, dtype=jnp.float32),
             "b": jnp.ones((64, 32), jnp.float32),
             "step": jnp.asarray(7, jnp.int32)}
    like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in state.items()}
    shardings = {k: SingleDeviceSharding(dev) for k in state}
    with CheckpointManager(tmpdir_path / "ckpt", n_io_ranks=2,
                           engine_config=EngineConfig(codec="blosc"),
                           parallel_io=2, device_compress=True) as mgr:
        mgr.save(state, 1, force=True)      # spawns the plane, compiles
        mgr.wait()
        with jax.profiler.trace(str(tmpdir_path / "trace")):
            mgr.save(state, 2, force=True)
            mgr.wait()
            restored, step = mgr.restore_latest(like, shardings)
            jax.block_until_ready(restored)
    assert step == 2
    for k in state:
        np.testing.assert_array_equal(np.asarray(restored[k]),
                                      np.asarray(state[k]))
    got = host_spans(tmpdir_path / "trace")
    assert got["jbp.ckpt_save"] == 1 and got["jbp.ckpt_write"] == 1, got
    assert got["jbp.device_shuffle"] == 2, got    # the two float32 leaves
    assert got["jbp.prepare"] == 1 and got["jbp.commit"] == 1, got
    assert got["jbp.restore"] == 1, got
    assert got["jbp.restore_fetch"] == len(state), got


def test_disabled_tracer_keeps_the_ring_empty_but_annotates(tmpdir_path):
    tr = DxtTracer()
    assert not tr.enabled
    jnp.ones(2).block_until_ready()
    with jax.profiler.trace(str(tmpdir_path / "trace")):
        with tr.span("commit", path="x") as sp:
            sp.length = 3
    assert tr.stats()["events"] == 0
    assert sp.seconds >= 0
    assert host_spans(tmpdir_path / "trace")["jbp.commit"] == 1


def test_enabled_tracer_records_the_same_span(tmpdir_path):
    tr = DxtTracer()
    tr.enable()
    jnp.ones(2).block_until_ready()
    with jax.profiler.trace(str(tmpdir_path / "trace")):
        with tr.span("prepare", path="p", rank=1):
            pass
        t = threading.Thread(target=lambda: tr.span("commit").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join()
    (rank, path, op, _, _, t0, t1), = [e[:7] for e in
                                       tr.snapshot()["events"]
                                       if e[2] == "prepare"]
    assert (rank, path) == (1, "p") and t1 >= t0
    assert {e[2] for e in tr.snapshot()["events"]} == {"prepare", "commit"}
    got = host_spans(tmpdir_path / "trace")
    assert got["jbp.prepare"] == 1 and got["jbp.commit"] == 1


def test_the_hook_is_found_once_jax_is_loaded(monkeypatch):
    """A tracer made before jax was loaded finds the hook on its first
    span after: the import order does not matter."""
    tr = DxtTracer()
    monkeypatch.delitem(sys.modules, "jax.profiler")
    assert tr._annotation() is None
    with tr.span("seal"):
        pass
    monkeypatch.undo()
    assert tr._annotation() is jax.profiler.TraceAnnotation
    assert dxt.TRACER._annotation() is jax.profiler.TraceAnnotation


def test_a_lost_hook_fails_the_span(monkeypatch):
    """jax loaded without `TraceAnnotation`: the span raises rather than
    leaving the trace in silence."""
    monkeypatch.setitem(sys.modules, "jax.profiler",
                        types.ModuleType("jax.profiler"))
    with pytest.raises(AttributeError, match="TraceAnnotation"):
        with DxtTracer().span("seal"):
            pass


def test_observe_span_feeds_the_histogram_on_success_only():
    tr = DxtTracer()
    METRICS.enable()
    with tr.span("seal", path="md.idx", length=10, observe=True):
        pass
    with tr.span("seal", path="md.idx", length=10):       # not observed
        pass
    with pytest.raises(ValueError):
        with tr.span("seal", path="md.idx", length=10, observe=True):
            raise ValueError("failed op")
    cell = METRICS.merged()["seal|md.idx"]
    assert cell["count"] == 1 and cell["sum_b"] == 10


def test_dxt_never_imports_jax():
    code = ("import sys\n"
            "from repro.core.dxt import TRACER\n"
            "with TRACER.span('commit'):\n"
            "    pass\n"
            "print('jax' in sys.modules)\n")
    src = str(pathlib.Path(dxt.__file__).resolve().parents[2])
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, check=True, env=env)
    assert r.stdout.strip() == "False"
