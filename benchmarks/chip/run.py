"""Chip benchmark: one run of one cell of BENCHMARK.json.

    python3 benchmarks/chip/run.py --workload NAME --seed N --seconds S --trace 0|1

Set-up (state from the seed, every program of the cell's traffic compiled
or loaded from the compile cache, writer processes spawned) is timed as
`setup_s`. Then the cell's traffic runs for `--seconds`. With `--trace 0`
the result carries the cell's end-to-end metrics; with `--trace 1` the
window runs under the profiler and the result carries its per-layer
metrics, each read by its own file under `metrics/`. Either way the
outputs are then checked against the plain reference under `reference/`.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`, each number compared beside its limit.
The same numbers are the last lines of standard error. Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result. Series, checkpoints and traces go to `.work/` beside
this file and are removed at exit.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import shutil
import sys
import time

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


class BenchError(RuntimeError):
    """A run that cannot give a result: it exits non-zero."""


def run(man: manifest.Manifest, workload: str, seed: int, seconds: float,
        trace: bool, *, devices, overrides=None, work=None) -> dict:
    """One run of `workload`; returns the result object. `overrides`
    replaces keys of the configuration and the traffic, and `work` the
    work directory (the tests' small sizes and temporary directories)."""
    import jax
    wl = man.workload(workload)
    cfg = man.config(wl["config"])
    traffic = man.traffic(wl["traffic"])
    for target, extra in zip((cfg, traffic), overrides or ({}, {})):
        target.update(extra)
    system = importlib.import_module(f"benchmarks.chip.systems.{cfg['system']}")
    work = pathlib.Path(work or chip.WORK / workload)
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    log = chip.CompileLog()
    cell = None
    try:
        t0 = time.perf_counter()
        cell = system.Cell(cfg, traffic, seed, work,
                           chip_key=chip.seed_key(seed))
        setup_s = time.perf_counter() - t0
        compiles = log.total()
        capture = reduce_trace.Capture(work / "trace") if trace else None
        if capture:
            capture.start()
        with jax.profiler.TraceAnnotation(reduce_trace.WINDOW):
            e2e = cell.window(seconds)
        if capture:
            capture.stop()
        window_compiles = log.total() - compiles
        peak = chip.device_peak_bytes(devices)
        checks, failed = cell.check()
        d = devices[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        result = {"correct": all(c["value"] <= c["limit"]
                                 for c in checks.values()),
                  "attempted": cell.attempted, "failed": failed}
        if trace:
            try:
                view = reduce_trace.View.load(capture.path,
                                              n_devices=len(devices))
            except reduce_trace.MissingSource as e:
                raise BenchError(f"the window's trace: {e}") from e
            metrics = per_layer(man, workload, reduce_trace.Context(
                view=view, counters=cell.counters, cfg=cfg, traffic=traffic,
                device_kind=d.device_kind))
            device["busy_s"] = view.busy_s
            device["window_s"] = view.window_s
            result.update(metrics=metrics, device=device,
                          breakdown=view.breakdown())
        else:
            metrics = {}
            for m in man.end_to_end(workload):
                value = setup_s if m["name"] == "setup_s" else e2e.get(m["name"])
                if value is None:
                    raise BenchError(f"the cell's traffic gave no "
                                     f"{m['name']!r}")
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            result.update(metrics=metrics, device=device)
        result["checks"] = checks
        print(f"window_compiles={window_compiles} counters="
              f"{json.dumps(cell.counters)}", file=sys.stderr)
        return result
    finally:
        if cell is not None:
            cell.close()
        log.close()
        shutil.rmtree(work, ignore_errors=True)


def per_layer(man, workload, ctx) -> dict:
    """Every per-layer metric the cell declares, read by its own file. A
    metric whose source is not there fails the run, naming it."""
    out = {}
    for m in man.per_layer(workload):
        try:
            value = man.reader(m["name"])(ctx)
        except reduce_trace.MissingSource as e:
            raise BenchError(f"per-layer metric {m['name']!r}: {e}") from e
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    wl = man.workload(args.workload)
    try:
        devices = chip.require_chip(wl["chips"])
    except chip.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    chip.enable_compile_cache()
    try:
        result = run(man, args.workload, args.seed, args.seconds,
                     bool(args.trace), devices=devices)
    except BenchError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
