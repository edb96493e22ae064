"""Small sizes at which the tests drive whole benchmark runs on the CPU,
with the chip check skipped."""
import jax

from benchpaths import ROOT
from benchmarks.chip import manifest
from benchmarks.chip.run import run

PIC = {"n_cells": 2500, "L": 0.025, "capacity": 1 << 16,
       "n_electrons": 25000, "n_ions": 25000, "n_neutrals": 25000}
CKPT = {"fsdp": 64, "leaves": {
    "params/a": {"shape": [64, 3072], "dtype": "float32"},
    "opt/m/a": {"shape": [64, 3072], "dtype": "float32"},
    "opt/v/a": {"shape": [64, 3072], "dtype": "float32"},
    "params/b": {"shape": [32, 128, 64], "dtype": "float32"},
    "opt/m/b": {"shape": [32, 128, 64], "dtype": "float32"},
    "step": {"shape": [], "dtype": "int32"}}}
SEED = 3_000_000_007


def small_run(workload, tmp_path, *, cfg=None, traffic=None, seed=SEED):
    cfg = cfg or (CKPT if workload.startswith("phi3") else PIC)
    return run(manifest.Manifest(ROOT), workload, seed, 0.5, False,
               devices=jax.devices(), overrides=(cfg, traffic or {}),
               work=tmp_path / "work")


def broken(result):
    """Names of the numbers that are over their limits."""
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])
