"""Whole checkpoint runs on the CPU with the timed path broken
underneath: each fault has to turn `correct` false."""
import numpy as np
import pytest

from faults import broken, small_run
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
