"""The trace reduction: on a small trace recorded on a TPU v5e, and on
synthetic traces with a module across the window's edge and with a
declared source absent."""
import json

import pytest

from benchpaths import ROOT
from benchmarks.chip import manifest, reduce_trace
from benchmarks.chip.run import BenchError, per_layer

DATA = ROOT / "tests" / "benchmark_chip" / "data"
PIC = "bit1_paper_share4.dump_every_chunk"


def synthetic(modules, spans=(), ops=()):
    return reduce_trace.View(
        [{"modules": list(modules), "ops": list(ops)}],
        [("bench.window", 1.0, 11.0), *spans])


STEP = "jit_pic_run_chunk(3856464242902868826)"


def straddling():
    return synthetic(
        [(STEP, 0.5, 1.5),             # shows as begun before the window
         (STEP, 2.0, 4.0),
         ("jit_searchsorted(6968025500868369776)", 4.0, 5.0),
         (STEP, 10.5, 11.5),           # shows as ending after it
         (STEP, 11.5, 12.0)],          # after the window: not its own
        spans=[("bench.flush", 2.0, 2.5), ("bench.flush", 10.8, 11.5),
               ("bench.flush", 0.2, 0.9),
               ("bench.call", 1.2, 9.0), ("bench.drain", 5.0, 6.0),
               ("np.asarray(jax.Array)", 7.0, 8.0)],
        ops=[("%byte_shuffle_block.1 = u8[4,262144] custom-call()", 5.5, 5.75),
             ("%fusion.14 = f32[8388609,3] fusion()", 2.0, 3.0)])


def test_module_across_the_edge_counts_whole():
    """On a v5e the host's and the device's clocks disagree a little at
    the window's edges: a module that overlaps the window is its own and
    counts whole; busy time is clipped to the window."""
    v = straddling()
    assert v.window_s == 10.0
    assert v.module_time("jit_pic_run_chunk") == 1.0 + 2.0 + 1.0
    assert v.busy_s == pytest.approx(0.5 + 2.0 + 1.0 + 0.5)
    assert v.busy_outside("jit_pic_run_chunk") == pytest.approx(1.0)
    assert v.span_mean("bench.flush") == pytest.approx((0.5 + 0.7) / 2)
    assert v.op_time("%byte_shuffle_block") == pytest.approx(0.25)


def test_breakdown_names_ops_and_gaps():
    b = straddling().breakdown()
    assert b["device_ops"][0] == ["jit_pic_run_chunk %fusion.14", 1.0]
    # a gap is named by the innermost host event around its middle
    assert b["idle_gaps"] == [["np.asarray(jax.Array)", pytest.approx(5.5)],
                              ["bench.call", pytest.approx(0.5)]]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_no_window_span_is_refused():
    with pytest.raises(reduce_trace.MissingSource, match="bench.window"):
        reduce_trace.View([{"modules": [(STEP, 0, 1)], "ops": []}], [])


COUNTERS = {"steps": 2, "chunks": 2, "capacity": 1 << 23, "flushes": 2,
            "drains": 1}


def ctx_for(view):
    man = manifest.Manifest(ROOT)
    return reduce_trace.Context(
        view=view, counters=COUNTERS, cfg=man.config("bit1_paper_share4"),
        traffic=man.traffic("dump_every_chunk"), device_kind="TPU v5 lite")


def test_absent_source_fails_the_run_naming_the_metric():
    only_diag = synthetic([("jit_searchsorted(6968025500868369776)", 2.0, 3.0)],
                          spans=[("bench.flush", 2.0, 2.5),
                                 ("bench.drain", 5.0, 6.0)])
    with pytest.raises(BenchError) as e:
        per_layer(manifest.Manifest(ROOT), PIC, ctx_for(only_diag))
    assert "pic_step_device_s" in str(e.value)
    assert "jit_pic_run_chunk" in str(e.value)


def test_absent_span_names_the_span():
    no_flush = synthetic([(STEP, 2.0, 4.0)])
    with pytest.raises(reduce_trace.MissingSource, match="bench.flush"):
        no_flush.span_mean("bench.flush")


def test_every_declared_metric_reads_a_complete_synthetic_trace():
    out = per_layer(manifest.Manifest(ROOT), PIC, ctx_for(straddling()))
    assert out["pic_step_device_s"]["value"] == 2.0
    assert out["device_idle_share.pic"]["value"] == pytest.approx(60.0)
    assert out["pic_step_roofline"]["value"] == pytest.approx(
        100 * 1_207_959_552 / 819e9 / 2.0)


def test_recorded_v5e_trace():
    """A tiny BIT1 window and one device shuffle, traced on a TPU v5e by
    `benchmarks/chip/record_test_trace.py`; the numbers it printed there."""
    want = json.loads((DATA / "tiny.json").read_text())
    v = reduce_trace.View.load(DATA / "tiny.xplane.pb", n_devices=1)
    got = {"window_s": v.window_s, "busy_s": v.busy_s,
           "pic_module_s": v.module_time(reduce_trace.PIC_MODULE),
           "outside_s": v.busy_outside(reduce_trace.PIC_MODULE),
           "shuffle_s": v.op_time(reduce_trace.SHUFFLE_KERNEL),
           "flush_s": v.span_mean("bench.flush"),
           "drain_s": v.span_mean("bench.drain")}
    for k, value in got.items():
        assert value == pytest.approx(want[k], rel=1e-9), k
    assert 0 < v.busy_s < v.window_s
    assert v.module_time(reduce_trace.PIC_MODULE) < v.busy_s
    b = v.breakdown()
    assert b["device_ops"] and b["idle_gaps"]
    assert (DATA / "tiny.xplane.pb").stat().st_size < 1_000_000
