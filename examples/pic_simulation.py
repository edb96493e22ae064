"""End-to-end driver — the paper's own kind of workload: a BIT1-style PIC-MC
ionization simulation streaming diagnostics (.dat analogue) and particle
dumps (.dmp analogue) through openPMD + the JBP(BP4) engine with
aggregation + blosc compression, monitored by the Darshan layer, with
checkpoint/restart.

    PYTHONPATH=src python examples/pic_simulation.py [--steps 2000]
"""
import argparse
import pathlib
import tempfile
import time

import jax

from repro.configs.bit1 import IO_KNOBS, cpu_config
from repro.core import EngineConfig, Series
from repro.core.darshan import MONITOR
from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint
from repro.launch.compile_cache import enable_compile_cache
from repro.pic.simulation import (diagnostics, init_sim, pic_run_chunk,
                                  write_diagnostics_openpmd,
                                  write_particle_dump_openpmd)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=2000)
    ap.add_argument("--mvstep", type=int, default=200,
                    help="diagnostic interval (paper: 1000)")
    ap.add_argument("--dmpstep", type=int, default=1000,
                    help="checkpoint interval (paper: 10000)")
    ap.add_argument("--scale", type=int, default=256,
                    help="paper-size divisor (100K cells / scale)")
    ap.add_argument("--n-io-ranks", type=int, default=16)
    args = ap.parse_args(argv)
    enable_compile_cache()

    workdir = pathlib.Path(tempfile.mkdtemp(prefix="repro-pic-"))
    cfg = cpu_config(args.scale)
    print(f"BIT1 use case (scaled 1/{args.scale}): {cfg.n_cells} cells, "
          f"3 species x {cfg.n_electrons} particles, {args.steps} steps")
    print(f"I/O knobs: mvstep={args.mvstep} dmpstep={args.dmpstep} "
          f"(paper: {IO_KNOBS['mvstep']}/{IO_KNOBS['dmpstep']})")

    MONITOR.reset()
    series = Series(workdir / "diag.bp4", "w", n_ranks=args.n_io_ranks,
                    engine_config=EngineConfig(aggregators=4, codec="blosc",
                                               workers=4))
    state = init_sim(cfg, jax.random.PRNGKey(0))
    t0 = time.time()
    for start in range(0, args.steps, args.mvstep):
        n = min(args.mvstep, args.steps - start)
        state = pic_run_chunk(state, cfg, n)
        write_diagnostics_openpmd(series, state, cfg,
                                  n_io_ranks=args.n_io_ranks)
        if int(state.step) % args.dmpstep == 0:
            write_particle_dump_openpmd(series, state, cfg,
                                        n_io_ranks=args.n_io_ranks)
            save_checkpoint(workdir / "ckpt", state._asdict(),
                            int(state.step), n_io_ranks=args.n_io_ranks)
        series.flush()
        d = diagnostics(state, cfg)
        print(f"  step {int(state.step):6d}  e={d['count/e']:9.0f} "
              f"D+={d['count/D_plus']:9.0f} D={d['count/D']:9.0f} "
              f"ionized={d['ionizations']:9.0f}")
    series.close()
    wall = time.time() - t0

    # restart proof: restore the last checkpoint and continue 100 steps
    back, at = restore_checkpoint(workdir / "ckpt",
                                  jax.tree_util.tree_map(lambda x: x,
                                                         state._asdict()))
    from repro.pic.simulation import PicState
    restored = PicState(**back)
    restored = pic_run_chunk(restored, cfg, 100)
    print(f"restart from step {at} OK -> continued to {int(restored.step)}")

    rep = MONITOR.report(args.n_io_ranks)
    print(f"\nwall={wall:.1f}s  bytes_written="
          f"{rep['total']['POSIX_BYTES_WRITTEN']/2**20:.1f}MiB  "
          f"files={MONITOR.total_files_written()}")
    cost = MONITOR.cost_per_process(args.n_io_ranks)
    print(f"darshan per-process: read={cost['read_s']:.4f}s "
          f"write={cost['write_s']:.4f}s meta={cost['meta_s']:.4f}s")
    print(f"openPMD series: {workdir / 'diag.bp4'}")


if __name__ == "__main__":
    main()
