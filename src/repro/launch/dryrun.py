import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_DRYRUN_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
# ^ MUST run before any other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell on the
production mesh with ShapeDtypeStruct stand-ins, then derive roofline terms.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--mesh single|multi|both]

Results accumulate in benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json —
reruns are incremental (use --force to recompute).
"""
import argparse
import json
import pathlib
import sys
import time
import traceback

import jax

from repro.configs.base import get_config, list_configs
from repro.launch import shapes as SH
from repro.launch.mesh import make_production_mesh, mesh_summary
from repro.launch.sharding import attn_layout
from repro.meshctx import use_mesh
from repro.optim.adamw import AdamWConfig
from repro.roofline.analysis import build_report
from repro.serve.steps import make_decode_step, make_prefill_step
from repro.train.step import make_train_step

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / "results" / "dryrun"

# Per-shape chunking (memory-lean attention for the 32k shapes) +
# gradient-accumulation depth for training (fits 16 GB HBM — §Perf it.5).
CHUNKS = {
    "train_4k": dict(q_chunk=1024, kv_chunk=1024, ssd_chunk=128,
                     microbatches=8),
    "prefill_32k": dict(q_chunk=1024, kv_chunk=1024, ssd_chunk=128),
    "decode_32k": dict(),
    "long_500k": dict(),
}


def step_fn_for(cfg, kind: str, shape_name: str, tuning: dict | None = None):
    ch = dict(CHUNKS.get(shape_name, {}))
    if tuning:
        ch.update(tuning)
    if kind == "train":
        return make_train_step(cfg, AdamWConfig(), **ch)
    if kind == "prefill":
        return make_prefill_step(cfg, **ch)
    return make_decode_step(cfg)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, tuning=None,
             verbose=True) -> dict:
    cfg = get_config(arch)
    ok, why = SH.applicable(cfg, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    spec = SH.input_specs(cfg, shape_name, mesh)
    fn = step_fn_for(cfg, spec["kind"], shape_name, tuning)

    t0 = time.time()
    with use_mesh(mesh):
        jitted = jax.jit(fn, in_shardings=spec["in_shardings"],
                         donate_argnums=spec["donate_argnums"])
        lowered = jitted.lower(*spec["args"])
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    case = spec["case"]
    rep = build_report(arch=arch, shape=shape_name, mesh_name=mesh_kind,
                       n_devices=int(mesh.size), hlo_text=hlo, cfg=cfg,
                       kind=case.kind, seq=case.seq, batch=case.batch,
                       mem_stats=mem, xla_cost=cost)
    out = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "status": "ok", "kind": case.kind,
           "mesh_info": mesh_summary(mesh),
           "attn_layout": attn_layout(cfg, int(mesh.shape["model"])),
           "compile_s": t1 - t0,
           "memory_analysis": {
               "argument_bytes": float(mem.argument_size_in_bytes),
               "output_bytes": float(mem.output_size_in_bytes),
               "temp_bytes": float(mem.temp_size_in_bytes),
               "alias_bytes": float(mem.alias_size_in_bytes),
           },
           "roofline": rep.to_dict()}
    if verbose:
        r = rep
        print(f"[{arch} x {shape_name} x {mesh_kind}] compile={t1-t0:.1f}s "
              f"compute={r.compute_s*1e3:.3f}ms memory={r.memory_s*1e3:.3f}ms "
              f"collective={r.collective_s*1e3:.3f}ms dominant={r.dominant} "
              f"useful={r.useful_flops_ratio:.3f} mfu_bound={r.mfu_bound:.3f} "
              f"args={out['memory_analysis']['argument_bytes']/2**30:.2f}GiB "
              f"temp={out['memory_analysis']['temp_bytes']/2**30:.2f}GiB "
              f"fits={r.fits_hbm}")
    return out


def cell_path(arch, shape, mesh_kind) -> pathlib.Path:
    return RESULTS_DIR / f"{arch}__{shape}__{mesh_kind}.json"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    archs = list_configs() if (args.all or not args.arch) else [args.arch]
    shapes = list(SH.SHAPE_TABLE) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                path = cell_path(arch, shape, mesh_kind)
                if path.exists() and not args.force:
                    prev = json.loads(path.read_text())
                    if prev.get("status") in ("ok", "skipped"):
                        print(f"[{arch} x {shape} x {mesh_kind}] cached "
                              f"({prev['status']})")
                        continue
                try:
                    out = run_cell(arch, shape, mesh_kind)
                except Exception as e:  # noqa: BLE001
                    out = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "status": "error", "error": repr(e),
                           "traceback": traceback.format_exc()}
                    failures.append((arch, shape, mesh_kind, repr(e)))
                    print(f"[{arch} x {shape} x {mesh_kind}] ERROR: {e!r}")
                path.write_text(json.dumps(out, indent=1))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print("\ndry-run complete: all requested cells OK")


if __name__ == "__main__":
    main()
