"""Readings that the limits of `correct` are set from, on the chip, at a
cell's own size, many seeds in one process.

    python3 benchmarks/chip/control.py --workload NAME --seeds 1,2,3 --side program|control

`program`: for each seed, the cell's set-up, which makes the first call
of its traffic through the program, then the run's own check; prints the
numbers it compares. `control`: the same check with the control in the program's
place. For a BIT1 cell the control is the reference holding its state in
bfloat16 (the precision below the configuration's float32); for the
checkpoint cell it is the program's own lossy codec (`lossy:rel:1e-3`),
the lower precision it offers. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
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

from benchmarks.chip import chip, manifest  # noqa: E402

LOSSY = "lossy:rel:1e-3"


def readings(man, workload: str, seed: int, side: str, *,
             overrides=({}, {})) -> dict:
    """The numbers one seed gives, by name."""
    import importlib
    wl = man.workload(workload)
    cfg = man.config(wl["config"]) | overrides[0]
    traffic = man.traffic(wl["traffic"]) | overrides[1]
    if cfg["system"] == "pic" and side == "control":
        from benchmarks.chip.reference import pic as ref
        from benchmarks.chip.systems.pic import compare_first_call
        control = ref.Control(cfg, chip.seed_key(seed), ref.Draws())
        out = compare_first_call(cfg, traffic, seed, control,
                                 bool(traffic["dump_every"]))
        return {k: v for k, (v, _) in out.items()}
    if cfg["system"] == "ckpt" and side == "control":
        traffic = traffic | {"codec": LOSSY}
    system = importlib.import_module(f"benchmarks.chip.systems.{cfg['system']}")
    work = chip.WORK / f"control-{workload}"
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    cell = None
    try:
        # set-up makes the first call, the one the reference follows
        cell = system.Cell(cfg, traffic, seed, work,
                           chip_key=chip.seed_key(seed))
        checks, _ = cell.check()
        return ({k: c["value"] for k, c in checks.items()}
                | {k: v for k, v in cell.counters.items() if k == "event_gap"})
    finally:
        if cell is not None:
            cell.close()
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--side", choices=("program", "control"), required=True)
    args = ap.parse_args(argv)
    man = manifest.Manifest()
    chip.require_chip(man.workload(args.workload)["chips"])
    chip.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": args.workload, "side": args.side,
                          "seed": seed, "readings": readings(
                              man, args.workload, seed, args.side)}),
              flush=True)


if __name__ == "__main__":
    main()
