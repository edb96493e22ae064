"""`phi3_fsdp64.reshard_4to1` on the CPU: saved from four virtual devices
and restored onto one, in a process of its own (`reshard_run.py`). A
sound run is correct; each fault, and the lossy-codec control on three
seeds, turns `correct` false on the check named."""
import json
import os
import subprocess
import sys

import pytest

from benchpaths import ROOT
from benchmarks.chip.reference import ckpt as ref
from faults import broken
from test_bench_control import SEEDS

HERE = ROOT / "tests" / "benchmark_chip"
FAULTS = {"first_chunk_only": {"restore_off"},
          "altered_last_shard": {"payload_off"},
          "device0_box_only": {"payload_off", "restore_off"}}
CONTROLS = [f"lossy_codec:{s}" for s in SEEDS]


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    r = subprocess.run(
        [sys.executable, str(HERE / "reshard_run.py"),
         str(tmp_path_factory.mktemp("reshard")), "sound", *FAULTS, *CONTROLS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = {}
    for line in r.stdout.splitlines():
        if line.startswith("{"):
            d = json.loads(line)
            out[d["run"]] = d["result"]
    return out


def test_sound_run_is_correct(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["device"]["count"] == 4


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(runs, fault):
    r = runs[fault]
    assert not r["correct"]
    assert FAULTS[fault] <= set(broken(r)), r["checks"]


@pytest.mark.parametrize("control", CONTROLS)
def test_lossy_codec_fails(runs, control):
    r = runs[control]
    assert not r["correct"]
    assert {"restore_off", "payload_off"} <= set(broken(r)), r["checks"]


@pytest.mark.parametrize("boxes,once", [
    ([((0, 0), (4, 3))], True),
    ([((2, 0), (2, 3)), ((0, 0), (2, 3))], True),
    ([((0, 0), (4, 1)), ((0, 1), (4, 2))], True),
    ([((0, 0), (2, 3))], False),                        # half left out
    ([((0, 0), (2, 3)), ((1, 0), (2, 3))], False),      # overlap, right count
    ([((0, 0), (2, 3)), ((3, 0), (2, 3))], False),      # past the end
    ([], False)])
def test_reference_covers_once(boxes, once):
    assert ref.covers_once(boxes, (4, 3)) is once
