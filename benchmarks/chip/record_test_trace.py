"""Records the small trace that the tests of the reduction read: a tiny
BIT1 configuration through the PIC cell's own calls, then one device
shuffle, in one traced window on the chip.

    python3 benchmarks/chip/record_test_trace.py OUT.xplane.pb

Prints the reduction's numbers for the recorded trace as one JSON line;
the tests hold the reduction to them."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
# run as a script: the repository root and the program's sources in place
# of this directory, whose module names would shadow the standard library's
if sys.path and pathlib.Path(sys.path[0]).resolve() == HERE:
    sys.path.pop(0)
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from benchmarks.chip import chip, manifest, reduce_trace  # noqa: E402
from benchmarks.chip.systems import pic  # noqa: E402

TINY = {"n_cells": 1000, "L": 0.01, "capacity": 1 << 14,
        "n_electrons": 4000, "n_ions": 4000, "n_neutrals": 4000}
SHUFFLE_ITEMS = 1 << 18          # one 1 MiB codec block of float32


def record(out: pathlib.Path) -> dict:
    import glob
    import jax
    import jax.numpy as jnp
    from repro.core import compression
    chip.require_chip(1)
    chip.enable_compile_cache()
    man = manifest.Manifest()
    cfg = man.config("bit1_paper_share4") | TINY
    traffic = man.traffic("dump_every_chunk") | {"n_chunks": 1}
    work = chip.WORK / "test_trace"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    try:
        cell = pic.Cell(cfg, traffic, 1, work, chip_key=chip.seed_key(1))
        block = jnp.arange(SHUFFLE_ITEMS, dtype=jnp.float32)
        compression.device_array_payload(block, "blosc")
        # host events at level 1 only (the benchmark's spans among them),
        # so that the recorded trace stays small
        capture = reduce_trace.Capture(work / "trace", host_level=1)
        capture.start()
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
            cell.window(0.0)
            compression.device_array_payload(block, "blosc")
        capture.stop()
        cell.close()
        pb = sorted(glob.glob(f"{work}/trace/plugins/profile/*/*.xplane.pb"))[-1]
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(pb, out)
        view = reduce_trace.View.load(work / "trace", n_devices=1)
        return {"bytes": out.stat().st_size, "window_s": view.window_s,
                "busy_s": view.busy_s,
                "pic_module_s": view.module_time(reduce_trace.PIC_MODULE),
                "outside_s": view.busy_outside(reduce_trace.PIC_MODULE),
                "shuffle_s": view.op_time(reduce_trace.SHUFFLE_KERNEL),
                "flush_s": view.span_mean("bench.flush"),
                "drain_s": view.span_mean("bench.drain"),
                "counters": cell.counters}
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    print(json.dumps(record(pathlib.Path(sys.argv[1]))), flush=True)
