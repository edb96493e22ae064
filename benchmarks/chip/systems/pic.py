"""The BIT1 PIC-MC loop through the program's own entry points:
`init_sim`, `open_diagnostic_series` and repeated `run_with_diagnostics`
calls on one series, as the traffic file says.

Set-up makes the state on the device from the seed and makes the first
call: it compiles or loads every program of the traffic, spawns the
series' writer processes, and its steps are the ones the reference
follows. The window makes the same calls on the same state and series.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmarks.chip.reference import pic as ref


class SpanSeries:
    """The series with a profiler span around `flush()` and `drain()`;
    everything else passes through untouched."""

    def __init__(self, series):
        self._series = series
        self.flushes = 0
        self.drains = 0

    def __getattr__(self, name):
        return getattr(self._series, name)

    def flush(self):
        import jax
        self.flushes += 1
        with jax.profiler.TraceAnnotation("bench.flush"):
            return self._series.flush()

    def drain(self):
        import jax
        self.drains += 1
        with jax.profiler.TraceAnnotation("bench.drain"):
            return self._series.drain()


def pic_config(cfg: dict):
    from repro.pic.simulation import PicConfig
    names = {f.name for f in dataclasses.fields(PicConfig)}
    return PicConfig(**{k: v for k, v in cfg.items() if k in names})


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, work, *,
                 chip_key):
        import jax
        from repro.pic.simulation import init_sim, open_diagnostic_series
        self.cfg, self.traffic, self.seed, self.work = cfg, traffic, seed, work
        self.pc = pic_config(cfg)
        self.chunk_steps = traffic["n_chunks"] * traffic["steps_per_chunk"]
        self.path = work / "diag.bp4"
        self.state = jax.block_until_ready(init_sim(self.pc, chip_key))
        self.series = SpanSeries(open_diagnostic_series(
            self.path, n_io_ranks=traffic["n_io_ranks"],
            parallel_io=traffic["parallel_io"]))
        self.calls = 0
        self._call()
        self.window_steps = 0
        self.counters = {}

    def _call(self):
        import jax
        from repro.pic.simulation import run_with_diagnostics
        t = self.traffic
        with jax.profiler.TraceAnnotation("bench.call"):
            self.state = run_with_diagnostics(
                self.state, self.pc, self.series, n_chunks=t["n_chunks"],
                steps_per_chunk=t["steps_per_chunk"],
                dump_every=t["dump_every"], n_io_ranks=t["n_io_ranks"])
        self.calls += 1

    def window(self, seconds: float) -> dict:
        calls0, flushes0, drains0 = (self.calls, self.series.flushes,
                                     self.series.drains)
        t0 = time.perf_counter()
        while True:                 # whole calls, the last one past the end
            self._call()
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        calls = self.calls - calls0
        self.window_steps = calls * self.chunk_steps
        self.counters = {
            "calls": calls, "steps": self.window_steps,
            "chunks": calls * self.traffic["n_chunks"],
            "flushes": self.series.flushes - flushes0,
            "drains": self.series.drains - drains0,
            "capacity": self.pc.capacity, "wall_s": wall}
        return {"pic_steps_per_s": self.window_steps / wall}

    @property
    def attempted(self) -> int:
        return self.window_steps

    # ------------------------------------------------------------ checking
    def check(self) -> tuple[dict, int]:
        """Numbers compared, each {"value", "limit"}; and how many of the
        window's steps failed (records missing). Frees the device state
        before the reference runs."""
        import jax
        from repro.core.bp_engine import BpReader
        final = jax.device_get(self.state)
        self.state = None
        self.series.close()
        lim = self.cfg["limits"]
        dumps = bool(self.traffic["dump_every"])
        total = int(final.step)
        with BpReader(self.path) as reader:
            sealed = set(reader.valid_steps())
            chunk_ends = range(self.traffic["steps_per_chunk"], total + 1,
                               self.traffic["steps_per_chunk"])
            missing = [s for s in chunk_ends if s not in sealed]
            out = {"steps_missing": (len(missing), 0)}
            records = Records(reader)
            out.update(compare_first_call(self.cfg, self.traffic, self.seed,
                                          records, dumps))
            if dumps and total in sealed:
                out["dump_off"] = (dump_off(records, total, final), 0)
        if "event_gap" in out:
            self.counters["event_gap"] = out.pop("event_gap")[0]
        checks = {k: {"value": v, "limit": lim.get(k, l)}
                  for k, (v, l) in out.items()}
        failed = min(len(missing) * self.traffic["steps_per_chunk"],
                     self.window_steps)
        return checks, failed

    def close(self):
        if self.series is not None:
            self.series.close()
            self.series = None


class Records:
    """The records the program wrote, read back from its series."""

    def __init__(self, reader):
        self.reader = reader

    def dump(self, step: int) -> dict:
        return {n: self.reader.read_var(step, f"/data/{step}/particles/{n}")
                for n in ref.dump_record_names()}

    def meshes(self, step: int) -> dict:
        return {n: self.reader.read_var(step, f"/data/{step}/meshes/{n}")
                for n in ref.mesh_names()}


def dump_off(records: Records, step: int, final) -> int:
    """Elements of the last dump that differ, bit for bit, from the final
    device state it was taken from."""
    host = {name: {"x": sp.x, "v": sp.v, "w": sp.w, "alive": sp.alive}
            for name, sp in (("electrons", final.electrons),
                             ("ions", final.ions),
                             ("neutrals", final.neutrals))}
    want = ref.dump_records(host, np.float32)
    off = 0
    for name, got in records.dump(step).items():
        w = np.ascontiguousarray(want[name], np.float32)
        off += int(np.count_nonzero(
            np.ascontiguousarray(got, np.float32).view(np.uint32)
            != w.view(np.uint32)))
    return off


def compare_first_call(cfg: dict, traffic: dict, seed: int, records,
                       dumps: bool) -> dict:
    """The reference from the seed through the first call's steps, against
    the mesh records (and, with dumps, the particle records) that
    `records` gives for them: the program's, read back, or the control's.
    Returns name -> (value, default limit or None)."""
    from benchmarks.chip.chip import seed_key
    draws = ref.Draws()
    state = ref.init(cfg, seed_key(seed), draws)
    worst = {"mesh_gap": 0.0, "pos_gap_cells": 0.0, "vel_gap": 0.0,
             "alive_off": 0, "event_off": 0, "event_gap": 0.0}
    spc = traffic["steps_per_chunk"]
    for s in range(1, traffic["n_chunks"] * spc + 1):
        dump = records.dump(s) if dumps and s % spc == 0 else None
        decided = None
        if dump is not None:
            # the neutrals the program ionized: alive before, not after
            ev = (state["neutrals"]["alive"] > 0) & (dump["D/weighting"] <= 0)

            def decided(info, _ev=ev):
                certain, ambiguous = info["certain"], info["ambiguous"]
                worst["event_off"] += int(np.count_nonzero(
                    (certain != _ev) & ~ambiguous))
                apart = info["gap"][_ev != info["own"]]
                worst["event_gap"] = max(worst["event_gap"],
                                         float(apart.max(initial=0.0)))
                return certain | (ambiguous & _ev)
        state, _ = ref.advance(cfg, state, draws, decided=decided)
        if s % spc:
            continue
        want = ref.diagnostics(cfg, state)
        for name, got in records.meshes(s).items():
            worst["mesh_gap"] = max(worst["mesh_gap"],
                                    ref.l1_gap(got, want[name]))
        if dump is not None:
            g = ref.state_gaps(cfg, state, dump)
            worst["pos_gap_cells"] = max(worst["pos_gap_cells"],
                                         g["pos_gap_cells"])
            worst["vel_gap"] = max(worst["vel_gap"], g["vel_gap"])
            worst["alive_off"] += g["alive_off"]
    out = {"mesh_gap": (worst["mesh_gap"], None)}
    if dumps:
        # not compared: how far from p the events lie that the program
        # and the reference decide apart (the margin's evidence)
        out["event_gap"] = (worst["event_gap"], None)
        out["pos_gap_cells"] = (worst["pos_gap_cells"], None)
        out["vel_gap"] = (worst["vel_gap"], None)
        out["alive_off"] = (worst["alive_off"], 0)
        out["event_off"] = (worst["event_off"], 0)
    return out
