"""The benchmark's yardstick: byte counts at the cells' shapes against
numbers worked out by hand, and the table of peaks."""
import json

import pytest

from benchpaths import ROOT
from benchmarks.chip import counts, peaks


def config(name):
    return json.loads((ROOT / "benchmarks" / "chip" / "configs"
                       / f"{name}.json").read_text())


def test_pic_step_bytes_at_share4():
    cfg = config("bit1_paper_share4")
    # 3 species x 2^23 slots x 24 B, read once and written once
    assert cfg["capacity"] == 8_388_608
    assert counts.pic_step_bytes(cfg["capacity"]) == 1_207_959_552


def test_pic_share_is_a_quarter_of_the_paper():
    cfg = config("bit1_paper_share4")
    for key in ("n_cells", "capacity", "n_electrons", "n_ions", "n_neutrals"):
        assert cfg[key] * 4 == cfg["paper"][key], key
    assert cfg["L"] * 4 == cfg["paper"]["L"]
    # the paper's cell width and 100 particles per cell per species
    assert cfg["L"] / cfg["n_cells"] == cfg["paper"]["L"] / cfg["paper"]["n_cells"]
    assert cfg["n_electrons"] / cfg["n_cells"] == 100


def test_phi3_shard_bytes():
    cfg = config("phi3_fsdp64")
    shard = counts.shard_leaves(cfg["leaves"], cfg["fsdp"])
    assert counts.leaf_bytes(shard, dtype="float32") == 716_526_144
    assert counts.leaf_bytes(shard, dtype="int32") == 4      # replicated step
    # the whole state: 64 float32 shards and one step
    whole = counts.shard_leaves(cfg["leaves"], 1)
    assert counts.leaf_bytes(whole) == 64 * 716_526_144 + 4 == 45_857_673_220


@pytest.mark.parametrize("shape,want", [
    ((32128, 3072), (502, 3072)),
    ((32, 3072, 32, 96), (32, 48, 32, 96)),
    ((32, 8192, 3072), (32, 128, 3072)),
    ((3072,), (48,)),
    ((), ()),
])
def test_shard_shape(shape, want):
    assert counts.shard_shape(shape, 64) == want


def test_shard_shape_refuses_an_indivisible_leaf():
    with pytest.raises(ValueError, match="divisible by 64"):
        counts.shard_shape((32, 96), 64)


def test_shuffle_bytes():
    assert counts.shuffle_bytes(1 << 20) == 2 << 20


def test_peaks_known_and_unknown_kind():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        peaks.peak("TPU v9 imaginary", "hbm_bytes_per_s")
