"""The per-layer metrics that read the program's own `jbp.*` spans, on
synthetic views: the idle time the spans leave unexplained, spans across
the window's edge, and a program without any such span, whose traced run
still gives every declared metric a number."""
import json
import math

import pytest

from benchpaths import ROOT
from benchmarks.chip import manifest, program_spans, reduce_trace
from benchmarks.chip.run import per_layer

PIC = "bit1_paper_share4.dump_every_chunk"
DIAG = "bit1_paper_share4.diag_only"
CKPT = "phi3_fsdp64.save_restore"
STEP = "jit_pic_run_chunk(3856464242902868826)"
NEW = {"idle_unattributed_share.pic": [PIC],
       "idle_unattributed_share.ckpt": [CKPT]}


def view(modules, spans):
    return reduce_trace.View([{"modules": list(modules), "ops": []}],
                             [("bench.window", 1.0, 11.0), *spans])


def pic_view():
    """Two chunks in a window of 1..11 s; a diagnostics span begun before
    the window and a commit ended after it count whole, a prepare wholly
    before the window does not count."""
    return view(
        [(STEP, 1.0, 2.0), ("jit_histogram(1)", 2.0, 3.0),
         (STEP, 6.0, 7.0), ("jit_histogram(1)", 7.0, 8.0)],
        [("jbp.prepare", 0.1, 0.5),                       # not the window's
         ("jbp.pic_chunk", 1.0, 2.0), ("jbp.diagnostics", 0.5, 3.0),
         ("jbp.dump_copy", 3.0, 3.5), ("jbp.series_flush", 3.5, 3.6),
         ("jbp.prepare", 3.6, 5.6), ("jbp.commit", 5.6, 5.7),
         ("jbp.pic_chunk", 6.0, 7.0), ("jbp.diagnostics", 7.0, 8.0),
         ("jbp.dump_copy", 8.0, 8.3), ("jbp.series_flush", 8.3, 8.4),
         ("jbp.prepare", 8.4, 10.4), ("jbp.commit", 10.4, 11.2),
         ("bench.drain", 8.4, 11.0), ("PjitFunction(x)", 5.8, 5.9)])


def ckpt_view():
    """Two save/wait/restore cycles on an otherwise idle device."""
    return view(
        [("jit_shuffled_items(2)", 1.2, 1.3), ("jit_equal(3)", 10.0, 10.5)],
        [("jbp.ckpt_save", 1.0, 1.1), ("jbp.ckpt_write", 1.1, 4.0),
         ("jbp.device_shuffle", 1.1, 1.6), ("jbp.device_shuffle", 1.6, 1.9),
         ("jbp.prepare", 1.9, 3.9), ("jbp.commit", 3.9, 4.0),
         ("jbp.restore", 4.0, 5.5), ("jbp.restore_fetch", 4.1, 4.6),
         ("jbp.restore_fetch", 4.6, 5.3),
         ("jbp.ckpt_save", 5.5, 5.6), ("jbp.ckpt_write", 5.6, 8.0),
         ("jbp.device_shuffle", 5.6, 6.4), ("jbp.prepare", 6.4, 7.9),
         ("jbp.commit", 7.9, 8.0),
         ("jbp.restore", 8.0, 10.0), ("jbp.restore_fetch", 8.0, 9.0),
         ("bench.wait", 1.1, 4.0)])


def ctx(v, workload):
    man = manifest.Manifest(ROOT)
    wl = man.workload(workload)
    return reduce_trace.Context(
        view=v, counters={}, cfg=man.config(wl["config"]),
        traffic=man.traffic(wl["traffic"]), device_kind="TPU v5 lite")


def read(name, v, workload):
    return manifest.Manifest(ROOT).reader(name)(ctx(v, workload))


def test_idle_unattributed_share_pic():
    # idle 6 of the 10 s: 3..6 and 8..11. Spans cover 3..5.7 and 8..11:
    # 5.7..6 (0.3 s) is idle with no jbp.* span open (PjitFunction and
    # bench.drain are not the program's spans)
    got = read("idle_unattributed_share.pic", pic_view(), PIC)
    assert got == pytest.approx(100 * 0.3 / 6.0)


def test_idle_unattributed_share_ckpt():
    # idle 9.4 of the 10 s. The saves' writes and the restores enclose
    # other spans, so only their leaves count: the restores leave 4.0..4.1,
    # 5.3..5.5 and 9..10 bare, and 10.5..11 has no span at all (bench.wait
    # is not the program's)
    got = read("idle_unattributed_share.ckpt", ckpt_view(), CKPT)
    assert got == pytest.approx(100 * (0.1 + 0.2 + 1.0 + 0.5) / 9.4)


def test_an_enclosing_span_explains_no_idle_time_of_its_own():
    # the device idles 1..11 but for 1..2; the drain encloses a prepare on
    # another thread, and only the prepare's 3..5 is explained
    v = view([(STEP, 1.0, 2.0)],
             [("jbp.pic_chunk", 1.0, 2.0), ("jbp.series_drain", 2.0, 11.0),
              ("jbp.prepare", 3.0, 5.0)])
    assert program_spans.idle_unattributed_share(v) == pytest.approx(
        100 * 7.0 / 9.0)


def test_leaves_keep_spans_with_nothing_inside():
    spans = [("jbp.ckpt_write", 1.0, 4.0), ("jbp.prepare", 1.0, 3.0),
             ("jbp.commit", 3.0, 4.0), ("jbp.seal", 3.5, 3.5),
             ("jbp.snapshot", 5.0, 6.0), ("jbp.transport", 5.0, 6.0),
             ("jbp.restore", 5.5, 7.0)]
    assert program_spans.leaves(spans) == [
        ("jbp.prepare", 1.0, 3.0), ("jbp.seal", 3.5, 3.5),
        ("jbp.snapshot", 5.0, 6.0), ("jbp.transport", 5.0, 6.0),
        ("jbp.restore", 5.5, 7.0)]


def test_interval_helpers():
    assert program_spans.union([(3, 4), (1, 2), (1.5, 3)]) == [[1, 4]]
    assert program_spans.subtract([[0, 10]], [[1, 2], [5, 12]]) == [
        [0, 1], [2, 5]]
    assert program_spans.length([[0, 1], [2, 5]]) == 4
    v = view([(STEP, 1.0, 2.0)], [])
    assert program_spans.clipped(v, [("a", 0.0, 2.0), ("b", 11.0, 12.0),
                                     ("c", 10.0, 13.0)]) == [
        (1.0, 2.0), (10.0, 11.0)]


def test_idle_share_is_zero_when_the_device_never_idles():
    v = view([(STEP, 0.5, 11.5)], [("jbp.pic_chunk", 1.0, 11.0)])
    assert program_spans.idle_unattributed_share(v) == 0.0


def test_program_without_spans_leaves_all_idle_time_unexplained():
    """A program from before the spans, or one whose spans never reached
    the profiler: the share is a number, and it is all of the idle time."""
    old = view([(STEP, 1.0, 2.0)], [("bench.flush", 2.0, 2.5),
                                    ("bench.drain", 3.0, 4.0)])
    for name, cells in NEW.items():
        assert read(name, old, cells[0]) == pytest.approx(100.0), name


# A traced run of the program from before the `jbp.*` spans: its trace
# has the benchmark's own `bench.*` spans and the device lines only.
PARENT_TRACES = {
    PIC: ([(STEP, 1.0, 2.0), ("jit_searchsorted(1)", 2.0, 3.0)],
          [("bench.flush", 3.0, 3.5), ("bench.drain", 4.0, 9.0)],
          {"steps": 2, "chunks": 2, "capacity": 1 << 23}),
    DIAG: ([(STEP, 1.0, 2.0), ("jit_searchsorted(1)", 2.0, 3.0)],
           [("bench.flush", 3.0, 3.1), ("bench.drain", 4.0, 4.1)],
           {"steps": 2, "chunks": 2, "capacity": 1 << 23}),
    CKPT: ([("jit_shuffled_items(2)", 1.2, 1.3)],
           [("bench.save", 1.0, 1.1), ("bench.wait", 1.1, 9.0)],
           {"saves": 1, "blocked_s": 8.0, "shuffled_bytes": 1 << 20}),
}


@pytest.mark.parametrize("workload", list(PARENT_TRACES))
def test_a_trace_without_program_spans_gives_every_metric_a_number(
        workload):
    """The benchmark's traced runs use these files on the program from
    before the spans too: there no declared metric may read null."""
    modules, spans, counters = PARENT_TRACES[workload]
    ops = [("%byte_shuffle_block.1 = u8[4,262144] custom-call()", 1.2, 1.3)]
    v = reduce_trace.View([{"modules": modules, "ops": ops}],
                          [("bench.window", 1.0, 11.0), *spans])
    man = manifest.Manifest(ROOT)
    wl = man.workload(workload)
    out = per_layer(man, workload, reduce_trace.Context(
        view=v, counters=counters, cfg=man.config(wl["config"]),
        traffic=man.traffic(wl["traffic"]), device_kind="TPU v5 lite"))
    assert set(out) == {m["name"] for m in man.per_layer(workload)}
    for name, m in out.items():
        assert isinstance(m["value"], float), name
        assert math.isfinite(m["value"]), name


def test_manifest_declares_each_new_metric_for_accepted_cells_only():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    man = manifest.Manifest(ROOT)
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, cells in NEW.items():
        m = declared[name]
        assert m["source"] == "program_span", name
        assert m["workloads"] == cells, name
        assert man.reader_path(name).is_file(), name
        assert callable(man.reader(name)), name
        assert m["unit"] == ("%" if "share" in name else "s"), name
    # the new entries come after the accepted ones
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
