"""Whole checkpoint runs on the CPU with the timed path broken
underneath: each fault has to turn `correct` false."""
import numpy as np
import pytest

from faults import SEED, broken, small_run
from repro.ckpt import checkpoint
from repro.ckpt.manager import CheckpointManager
from repro.core import compression

CELL = "phi3_fsdp64.save_restore"


def test_sound_run_is_correct(tmp_path):
    r = small_run(CELL, tmp_path)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0


def stale_saves(monkeypatch):
    """Every save writes the first state it was given: the state the
    checkpoint holds never changes."""
    real, first = CheckpointManager.save, {}

    def save(self, state, step, **kw):
        return real(self, first.setdefault("state", state), step, **kw)
    monkeypatch.setattr(CheckpointManager, "save", save)


def half_the_leaves(monkeypatch):
    real = checkpoint.save_checkpoint

    def save(directory, state, step, **kw):
        names = sorted(state)
        return real(directory, {k: state[k] for k in names[::2]}, step, **kw)
    monkeypatch.setattr(checkpoint, "save_checkpoint", save)


def altered_block(monkeypatch):
    real = compression.device_precondition

    def precondition(arr, **kw):
        chunk = real(arr, **kw)
        chunk.data = chunk.data.copy()
        chunk.data[0] ^= np.uint8(1)
        return chunk
    monkeypatch.setattr(compression, "device_precondition", precondition)


@pytest.mark.parametrize("fault,caught", [
    (stale_saves, {"restore_off"}),
    (half_the_leaves, {"restore_off"}),
    (altered_block, {"restore_off", "payload_off"})])
def test_fault_makes_the_run_incorrect(fault, caught, tmp_path, monkeypatch):
    fault(monkeypatch)
    r = small_run(CELL, tmp_path)
    assert not r["correct"]
    assert caught <= set(broken(r))


# a state with a bfloat16 leaf, which the program keeps off the device
# shuffle and saves from the host in row chunks; both floating leaves are
# sampled
BF16 = {"fsdp": 64, "leaves": {
    "params/h": {"shape": [512, 1024], "dtype": "bfloat16"},
    "params/a": {"shape": [64, 3072], "dtype": "float32"},
    "step": {"shape": [], "dtype": "int32"}},
    "limits": {"restore_off": 0, "payload_off": 0, "payload_leaves": 2}}


def altered_bf16_block(monkeypatch):
    """One item of each bfloat16 chunk handed to the writers altered."""
    real = checkpoint._to_storage

    def to_storage(arr):
        out = real(arr)
        if out.dtype == np.uint16:
            out = out.copy()
            out.flat[0] ^= np.uint16(1)
        return out
    monkeypatch.setattr(checkpoint, "_to_storage", to_storage)


def test_bfloat16_leaf_is_correct(tmp_path):
    r = small_run(CELL, tmp_path, cfg=BF16)
    assert r["correct"], r["checks"]


def test_altered_bfloat16_block_is_caught(tmp_path, monkeypatch):
    altered_bf16_block(monkeypatch)
    r = small_run(CELL, tmp_path, cfg=BF16)
    assert not r["correct"]
    assert "payload_off" in broken(r), r["checks"]


def test_bfloat16_leaf_is_drawn_at_random():
    import jax
    from benchmarks.chip import chip, counts
    from benchmarks.chip.systems.ckpt import layout, make_state
    leaves = counts.shard_leaves(BF16["leaves"], BF16["fsdp"])
    state = make_state(leaves, {"param_std": 0.02, "grad_std": 1e-3},
                       chip.seed_key(SEED), layout(leaves, jax.devices()[:1]))
    h = np.asarray(state["params/h"], np.float32)
    assert state["params/h"].dtype == "bfloat16"
    assert np.unique(h).size > 100 and 0.01 < h.std() < 0.03
