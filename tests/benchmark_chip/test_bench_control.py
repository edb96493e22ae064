"""The controls of `correct`, at a size a test can hold: each has to come
out not correct, on three seeds. For BIT1 the control is the reference
with its state in bfloat16 in the program's place; for the checkpoint,
the program's own lossy codec."""
import pytest

from benchpaths import ROOT
from benchmarks.chip import chip, manifest
from benchmarks.chip.control import LOSSY
from benchmarks.chip.reference import pic as ref
from benchmarks.chip.systems.pic import compare_first_call
from faults import PIC, broken, small_run

SEEDS = [3_000_000_011, 2**33 + 5, 17]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("traffic", ["dump_every_chunk", "diag_only"])
def test_bfloat16_reference_in_the_programs_place_fails(traffic, seed):
    man = manifest.Manifest(ROOT)
    cfg = man.config("bit1_paper_share4") | PIC
    tr = man.traffic(traffic)
    control = ref.Control(cfg, chip.seed_key(seed), ref.Draws())
    out = compare_first_call(cfg, tr, seed, control, bool(tr["dump_every"]))
    over = [k for k, (v, default) in out.items()
            if k in cfg["limits"] and v > cfg["limits"][k]]
    assert "mesh_gap" in over, out
    if tr["dump_every"]:
        assert "pos_gap_cells" in over, out


@pytest.mark.parametrize("seed", SEEDS)
def test_lossy_codec_in_the_checkpoint_fails(seed, tmp_path):
    r = small_run("phi3_fsdp64.save_restore", tmp_path,
                  traffic={"codec": LOSSY}, seed=seed)
    assert not r["correct"]
    assert {"restore_off", "payload_off"} <= set(broken(r))
