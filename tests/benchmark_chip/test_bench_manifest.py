"""BENCHMARK.json against the rules the harness relies on: names, units,
files found by name, metrics that move what their cells report, and a
harness that needs no edit for a new cell or metric."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchpaths import ROOT
from benchmarks.chip import manifest

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_command():
    assert set(BENCH) == KEYS
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert BENCH["paths"] == ["benchmarks/chip", "tests/benchmark_chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def all_names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[group]:
            yield entry["name"]
    for wl in BENCH["workloads"]:
        yield wl["config"]
        yield wl["traffic"]
    for c in BENCH["configs"]:
        yield from c["reduced"]


@pytest.mark.parametrize("name", sorted(set(all_names())))
def test_name_characters(name):
    assert manifest.NAME.match(name), name


def test_units_lines_and_uniqueness():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    for m in metrics:
        assert manifest.UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for group in ("configs", "workloads"):
        for e in BENCH[group]:
            assert LINE.match(e["why"])
    for c in BENCH["configs"]:
        assert LINE.match(c["source"]) and c["source"].startswith("https://")
    for m in BENCH["per_layer"]:
        assert LINE.match(m["layer"])
    for group in (BENCH["configs"], BENCH["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


def test_every_file_is_found_by_name():
    man = manifest.Manifest(ROOT)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith("benchmarks/chip/")
    for wl in BENCH["workloads"]:
        cfg = man.config(wl["config"])
        traffic = man.traffic(wl["traffic"])
        assert traffic["system"] == cfg["system"]
        assert (ROOT / "benchmarks/chip/systems" / f"{cfg['system']}.py").is_file()
        assert wl["chips"] in (1, 4)
    for m in BENCH["per_layer"]:
        assert callable(man.reader(m["name"]))


def test_each_cell_reports_what_its_metrics_move():
    man = manifest.Manifest(ROOT)
    for wl in BENCH["workloads"]:
        e2e = {m["name"] for m in man.end_to_end(wl["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2, wl["name"]
        layer = man.per_layer(wl["name"])
        assert layer, wl["name"]
        for m in layer:
            assert m["moves"] in e2e, (wl["name"], m["name"])
    for m in BENCH["per_layer"]:
        assert m["workloads"], m["name"]
        assert {e["name"] for e in BENCH["end_to_end"]} >= {m["moves"]}
    # a configuration's every key changed from its source is listed
    for c in BENCH["configs"]:
        cfg = man.config(c["name"])
        paper = cfg.get("paper", {})
        changed = {k for k, v in paper.items() if cfg.get(k) != v}
        assert changed == set(c["reduced"]), c["name"]


def test_four_chip_cells_are_at_most_half_or_one():
    four = [wl["name"] for wl in BENCH["workloads"] if wl["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2), four


@pytest.mark.parametrize("cell", ["phi3_fsdp64.reshard_4to1"])
def test_a_layout_cell_is_found_by_name(cell):
    """The cell's configuration and traffic are files found by name, its
    traffic is save_restore's with the two layouts added, and it asks for
    as many chips as its layouts use."""
    man = manifest.Manifest(ROOT)
    wl = man.workload(cell)
    assert man.traffic_path(wl["traffic"]).is_file()
    traffic = man.traffic(wl["traffic"])
    base = man.traffic("save_restore")
    layouts = {"save_chips", "restore_chips"}
    assert {k: v for k, v in traffic.items() if k not in layouts | {"about"}} == {
        k: v for k, v in base.items() if k != "about"}
    assert max(traffic[k] for k in layouts) == wl["chips"]
    e2e = {m["name"] for m in man.end_to_end(cell)}
    assert e2e == {"ckpt_save_s", "ckpt_restore_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(cell)} == {
        m["name"] for m in man.per_layer("phi3_fsdp64.save_restore")}


def copy_benchmark(tmp_path):
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_a_new_cell_and_metric_need_only_new_files(tmp_path):
    root = copy_benchmark(tmp_path)
    here = root / "benchmarks" / "chip"
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    (here / "traffic" / "paper_ratio.json").write_text(json.dumps(
        manifest.Manifest(root).traffic("dump_every_chunk") | {"dump_every": 10}))
    (here / "metrics" / "chunk_count.py").write_text(
        "def read(ctx):\n    return float(ctx.counters['chunks'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": "bit1_paper_share4.paper_ratio", "config": "bit1_paper_share4",
        "traffic": "paper_ratio", "chips": 1, "why": "one dump per ten chunks"})
    bench["per_layer"].append({
        "name": "chunk_count", "unit": "chunks", "better": "higher",
        "source": "program_counter", "layer": "PIC step",
        "moves": "pic_steps_per_s",
        "workloads": ["bit1_paper_share4.paper_ratio"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    man = manifest.Manifest(root)
    assert man.traffic(man.workload("bit1_paper_share4.paper_ratio")["traffic"])[
        "dump_every"] == 10
    assert [m["name"] for m in man.per_layer("bit1_paper_share4.paper_ratio")] == [
        "chunk_count"]
    ctx = type("Ctx", (), {"counters": {"chunks": 4}})()
    assert man.reader("chunk_count")(ctx) == 4.0
    assert {p: p.read_bytes() for p in before} == before      # nothing edited


def run_py(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=240)


ARGS = ("--workload", "phi3_fsdp64.save_restore", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0")


def test_run_refuses_without_a_tpu():
    r = run_py(ROOT, *ARGS)
    assert r.returncode != 0
    assert "JAX found no TPU" in r.stderr
    assert r.stdout.strip() == ""


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    r = run_py(copy_benchmark(tmp_path), *ARGS)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
