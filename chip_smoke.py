"""Smoke run of the main path on a TPU, through the entry points a user calls.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # sharded checkpoint path only

One chip: the BIT1 PIC-MC loop at `paper_config()` size (100K cells,
3 species x 2^25 slots) through `run_with_diagnostics`, with diagnostics
every chunk and one full particle dump through the multi-process write
plane (`parallel_io=2`); the dump is read back bit for bit. Then the
jnp physics against the same functions on the CPU, the on-chip shuffle
against the host encoder, and a `device_compress` checkpoint, restored
and run on, against the live state.

`--chips 4`: the particle state sharded over four chips along the
capacity axis, saved with `parallel_io=2, device_compress=True`, and
restored onto two chips and onto one, bit for bit.

Every line but the last is `key=value`. The last line is one JSON object,
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
A failed check raises, so the script exits non-zero without that line;
so does a run where JAX finds no TPU. The compile cache is
`JAX_COMPILATION_CACHE_DIR` when set, else `.jax_cache/` in the checkout.
Series and checkpoints go to `.smoke/` in the checkout and are removed.
"""
from __future__ import annotations

import argparse
import collections
import functools
import json
import pathlib
import shutil
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

WORK = ROOT / ".smoke"
SEED = 0
N_CHUNKS = 4              # the one particle dump is at the last chunk
STEPS_PER_CHUNK = 2       # one value for every chunk: pic_run_chunk compiles once
N_IO_RANKS = 16
PARALLEL_IO = 2
SPECIES = (("e", "electrons"), ("D_plus", "ions"), ("D", "neutrals"))


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str):
    if not ok:
        raise SmokeFailure(what)


def emit(key: str, value):
    print(f"{key}={value}", flush=True)


def require_tpu(n_chips: int):
    """The first `n_chips` TPU devices; fails on any other backend."""
    import jax
    backend = jax.default_backend()
    check(backend == "tpu", f"JAX found no TPU (default backend {backend!r})")
    devices = jax.devices()
    check(len(devices) >= n_chips,
          f"need {n_chips} TPU chips, JAX reports {len(devices)}")
    emit("device_kind", devices[0].device_kind)
    emit("device_count", len(devices))
    return devices[:n_chips]


class CompileLog:
    """XLA compiles per jitted function (loads from the persistent cache
    included) and their seconds, from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = collections.Counter()
        self.seconds = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            name = kw.get("fun_name", "?")
            self.count[name] += 1
            self.seconds[name] += duration

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class ChunkClock:
    """Reducer hook of `run_with_diagnostics`: stamps the end of every
    chunk and keeps the diagnostic arrays the last chunk wrote."""

    def __init__(self):
        self.stamps: list[float] = []
        self.last: dict = {}

    def update(self, step, arrays):
        self.stamps.append(time.perf_counter())
        self.last = arrays


def bits_equal(a, b) -> bool:
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    return (a.shape == b.shape and a.dtype == b.dtype and np.array_equal(
        np.ascontiguousarray(a).reshape(-1).view(np.uint8),
        np.ascontiguousarray(b).reshape(-1).view(np.uint8)))


def dump_records(sp) -> dict:
    """The particle records a dump writes for one species, from a host
    copy: `weighting` is `w * alive`, as `write_particle_dump_openpmd`."""
    return {"position/x": sp.x, "momentum/x": sp.v[:, 0],
            "momentum/y": sp.v[:, 1], "momentum/z": sp.v[:, 2],
            "weighting": sp.w * sp.alive}


def particle_totals(state) -> dict:
    """Per species: (alive count, alive weight in float64), on the host."""
    import jax
    import numpy as np
    out = {}
    for name, field in SPECIES:
        sp = getattr(state, field)
        w, alive = jax.device_get((sp.w, sp.alive))
        out[name] = (int(np.count_nonzero(alive)),
                     float(np.sum(w.astype(np.float64) * alive)))
    return out


def device_peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# ------------------------------------------------------------------ phases
def phase_reference(state, cfg):
    """`deposit_cic` and `push` on the default device against the same
    functions on the CPU, on the electrons of `state`."""
    import jax
    import numpy as np
    from repro.pic import grid
    from repro.pic.particles import push

    @functools.partial(jax.jit, static_argnums=(2,))
    def physics(sp, E, cfg):
        rho = grid.deposit_cic(sp.x, sp.w, sp.alive, cfg.n_cells, cfg.dx)
        moved, _ = push(sp, grid.gather_field(E, sp.x, cfg.dx), cfg.dt,
                        cfg.L, boundary=cfg.boundary)
        return rho, moved.x, moved.v

    cells = (np.arange(cfg.n_cells) + 0.5) / cfg.n_cells
    E = (10.0 * np.sin(2 * np.pi * cells)).astype(np.float32)
    cpu = jax.devices("cpu")[0]
    rho, x, v = jax.device_get(physics(state.electrons, E, cfg))
    rho_r, x_r, v_r = jax.device_get(
        physics(jax.device_put(state.electrons, cpu), jax.device_put(E, cpu),
                cfg))
    np.testing.assert_allclose(rho, rho_r, rtol=1e-5,
                               atol=1e-5 * float(np.abs(rho_r).max()))
    np.testing.assert_allclose(v, v_r, rtol=1e-6, atol=1e-6)
    dx = np.abs(x.astype(np.float64) - x_r)         # positions wrap at L
    check(np.minimum(dx, cfg.L - dx).max() <= 1e-6,
          "push positions differ from the CPU reference")
    emit("reference_deposit_max_rel_err",
         float(np.abs(rho - rho_r).max() / np.abs(rho_r).max()))


def phase_pic(state, cfg, path, *, n_chunks: int, steps_per_chunk: int):
    """The BIT1 loop through `run_with_diagnostics`: diagnostics every
    chunk, one particle dump at the last chunk through the parallel write
    plane. Returns (state, last chunk's diagnostics, timings)."""
    from repro.pic.simulation import open_diagnostic_series, run_with_diagnostics
    clock = ChunkClock()
    series = open_diagnostic_series(path, n_io_ranks=N_IO_RANKS,
                                    parallel_io=PARALLEL_IO)
    try:
        t0 = time.perf_counter()
        state = run_with_diagnostics(
            state, cfg, series, n_chunks=n_chunks,
            steps_per_chunk=steps_per_chunk, dump_every=n_chunks,
            n_io_ranks=N_IO_RANKS, reducers=clock)
        t_end = time.perf_counter()
    finally:
        series.close()
    check(len(clock.stamps) == n_chunks, "a chunk did not report")
    walls = [b - a for a, b in zip([t0] + clock.stamps, clock.stamps)]
    steady = walls[1:-1] or walls[:1]
    timings = {"chunk_walls_s": walls,
               # the dump chunk against the chunks that only write meshes
               "dump_stall_s": walls[-1] - statistics.median(steady),
               "drain_s": t_end - clock.stamps[-1]}
    return state, clock.last, timings


def phase_readback(path, host, diag_last: dict, diag_now: dict):
    """Every particle record of the dump equals the host copy of the state
    bit for bit; the last chunk's mesh records equal `diagnostics()`."""
    from repro.core.bp_engine import BpReader
    step = int(host.step)
    with BpReader(path) as reader:
        check(step in reader.valid_steps(), f"dump step {step} not sealed")
        for name, field in SPECIES:
            for rec, arr in dump_records(getattr(host, field)).items():
                var = f"/data/{step}/particles/{name}/{rec}"
                check(bits_equal(reader.read_var(step, var), arr),
                      f"{var} differs from the state")
        check(diag_last.keys() == {k for k, v in diag_now.items()
                                   if hasattr(v, "shape")},
              "diagnostic arrays differ in name")
        for name, arr in diag_last.items():
            var = f"/data/{step}/meshes/{name.replace('/', '_')}"
            got = reader.read_var(step, var)
            check(bits_equal(got, arr) and bits_equal(got, diag_now[name]),
                  f"{var} differs from diagnostics()")


def check_physics(before: dict, after: dict, ionizations: int):
    """Neutral plus ion weight is conserved; no spawn was dropped."""
    check(after["D"][1] + after["D_plus"][1]
          == before["D"][1] + before["D_plus"][1],
          f"D + D+ weight not conserved: {before} -> {after}")
    check(ionizations > 0, "no ionization happened")
    for name, sign in (("e", 1), ("D_plus", 1), ("D", -1)):
        check(after[name][0] - before[name][0] == sign * ionizations,
              f"{name}: {before[name][0]} -> {after[name][0]} alive with "
              f"{ionizations} ionizations (a spawn was dropped)")


def phase_device_payload(state):
    """The on-chip shuffle's payload equals the host encoder's, byte for
    byte, for one particle record of the live state."""
    import numpy as np
    from repro.core import compression as C
    arr = state.electrons.x
    dev, stats = C.device_array_payload(arr, "blosc")
    check(dev == C.array_payload(np.asarray(arr), "blosc"),
          "device and host blosc payloads differ")
    check(stats.device_bytes == arr.nbytes, "not every byte was shuffled on-chip")
    emit("device_payload_bytes", len(dev))


def check_kernel_compiled():
    """The write-path kernel compiles to a Mosaic custom call, so it runs
    compiled and not in interpret mode."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import auto_interpret
    from repro.kernels.bitshuffle.kernel import byte_shuffle_block
    check(auto_interpret() is False, "kernels would run interpreted")
    block = jax.ShapeDtypeStruct((1 << 20,), jnp.uint8)
    text = byte_shuffle_block.lower(block, itemsize=4,
                                    interpret=False).compile().as_text()
    check("tpu_custom_call" in text, "no tpu_custom_call in the kernel's HLO")


def phase_checkpoint(state, cfg, directory, host, *, steps_per_chunk: int):
    """`device_compress` checkpoint, restore bit for bit, and one more
    chunk from the restored and from the live state, bit for bit."""
    import jax
    from repro.ckpt.checkpoint import (flatten_state, restore_checkpoint,
                                       save_checkpoint)
    from repro.core.bp_engine import EngineConfig
    from repro.core.darshan import MONITOR
    from repro.pic.simulation import PicState, pic_run_chunk

    step = int(host.step)
    before = MONITOR.report()["total"]
    t0 = time.perf_counter()
    save_checkpoint(directory, state._asdict(), step, n_io_ranks=N_IO_RANKS,
                    engine_config=EngineConfig(codec="blosc"),
                    device_compress=True)
    out = {"ckpt_save_s": time.perf_counter() - t0}
    after = MONITOR.report()["total"]
    for key in ("COMPRESS_DEVICE_BYTES", "POSIX_BYTES_WRITTEN"):
        out[key] = after.get(key, 0.0) - before.get(key, 0.0)
    check(out["COMPRESS_DEVICE_BYTES"] > 0, "no checkpoint byte went on-chip")

    t0 = time.perf_counter()
    back, at = restore_checkpoint(directory, state._asdict())
    out["ckpt_restore_s"] = time.perf_counter() - t0
    check(at == step, f"restored step {at}, saved {step}")
    want = flatten_state(host._asdict())
    for name, leaf in flatten_state(back).items():
        check(bits_equal(leaf, want[name]), f"restored {name} differs")
    restored = jax.device_put(PicState(**back))
    del back

    def timed_chunk(s):
        t = time.perf_counter()
        s = jax.block_until_ready(pic_run_chunk(s, cfg, steps_per_chunk))
        return s, time.perf_counter() - t

    resumed, out["chunk_s_restored"] = timed_chunk(restored)
    del restored
    resumed = jax.device_get(resumed)
    live, out["chunk_s_live"] = timed_chunk(state)
    live = jax.device_get(live)
    want = flatten_state(live)
    for name, leaf in flatten_state(resumed).items():
        check(bits_equal(leaf, want[name]),
              f"{name} after a chunk from the restored state differs")
    return out


def phase_sharded(cfg, directory, devices):
    """The particle state sharded over `devices` along the capacity axis
    (scalars and key replicated), saved with `parallel_io=2,
    device_compress=True`, restored onto two devices and onto one."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    from repro.ckpt.checkpoint import (checkpoint_path, flatten_state,
                                       restore_sharded, save_checkpoint)
    from repro.core.bp_engine import BpReader, EngineConfig
    from repro.core.darshan import MONITOR
    from repro.launch.mesh import make_mesh
    from repro.pic.simulation import init_sim

    def layout(tree, mesh):
        return jax.tree.map(
            lambda x: NamedSharding(
                mesh, P("capacity") if x.shape[:1] == (cfg.capacity,)
                else P()), tree)

    state = init_sim(cfg, jax.random.PRNGKey(SEED))._asdict()
    host = jax.device_get(state)
    mesh = make_mesh((len(devices),), ("capacity",), devices=devices)
    placed = jax.device_put(state, layout(state, mesh))
    del state
    before = MONITOR.report()["total"].get("COMPRESS_DEVICE_BYTES", 0.0)
    t0 = time.perf_counter()
    save_checkpoint(directory, placed, 0, n_io_ranks=8,
                    engine_config=EngineConfig(codec="blosc"),
                    parallel_io=PARALLEL_IO, device_compress=True)
    out = {"sharded_save_s": time.perf_counter() - t0,
           "COMPRESS_DEVICE_BYTES":
               MONITOR.report()["total"].get("COMPRESS_DEVICE_BYTES", 0.0)
               - before}
    check(out["COMPRESS_DEVICE_BYTES"] > 0, "no shard went through the chip")
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        placed)
    del placed

    want = flatten_state(host)
    with BpReader(checkpoint_path(directory, 0)) as reader:
        for name, leaf in want.items():
            boxes = [(c.offset, c.extent)
                     for c in reader.iter_chunks(0, f"state/{name}")]
            check(len(set(boxes)) == len(boxes)
                  and sum(int(np.prod(e)) for _, e in boxes)
                  == max(leaf.size, 1),
                  f"{name}: boxes {boxes} do not tile the leaf once")
            if leaf.shape[:1] == (cfg.capacity,):
                check(len(boxes) == len(devices),
                      f"{name}: {len(boxes)} boxes for {len(devices)} shards")

    targets = {"two_devices": layout(like, make_mesh(
                   (2,), ("capacity",), devices=devices[:2])),
               "one_device": jax.tree.map(
                   lambda _: SingleDeviceSharding(devices[0]), like)}
    for label, shardings in targets.items():
        t0 = time.perf_counter()
        got, at = restore_sharded(directory, like, shardings)
        out[f"restore_{label}_s"] = time.perf_counter() - t0
        check(at == 0, f"restored step {at}")
        for name, leaf in flatten_state(got).items():
            check(leaf.sharding.is_equivalent_to(flatten_state(shardings)[name],
                                                 leaf.ndim),
                  f"{label}: {name} not on the requested layout")
            check(bits_equal(jax.device_get(leaf), want[name]),
                  f"{label}: restored {name} differs")
        del got
    return out


# -------------------------------------------------------------- entry points
def run_one_chip(device):
    import jax
    from repro.configs.bit1 import paper_config
    from repro.core.darshan import MONITOR
    from repro.launch.compile_cache import enable_compile_cache
    from repro.pic.simulation import diagnostics, init_sim

    emit("compile_cache_dir", enable_compile_cache())
    log = CompileLog()
    cfg = paper_config()
    emit("config", f"paper_config n_cells={cfg.n_cells} "
                   f"capacity={cfg.capacity} chunks={N_CHUNKS} "
                   f"steps_per_chunk={STEPS_PER_CHUNK} "
                   f"parallel_io={PARALLEL_IO}")
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    try:
        state = init_sim(cfg, jax.random.PRNGKey(SEED))
        phase_reference(state, cfg)
        before = particle_totals(state)

        MONITOR.reset()
        state, diag_last, t = phase_pic(state, cfg, WORK / "diag.bp4",
                                        n_chunks=N_CHUNKS,
                                        steps_per_chunk=STEPS_PER_CHUNK)
        emit("chunk_walls_s", json.dumps(t["chunk_walls_s"]))
        emit("dump_stall_s", t["dump_stall_s"])
        emit("drain_s", t["drain_s"])
        emit("dump_host_bytes", 3 * 5 * cfg.capacity * 4)
        emit("darshan_bytes_written_series",
             MONITOR.report()["total"].get("POSIX_BYTES_WRITTEN", 0.0))

        host = jax.device_get(state)
        ionizations = int(host.total_ionizations)
        check_physics(before, particle_totals(host), ionizations)
        emit("ionizations", ionizations)
        phase_readback(WORK / "diag.bp4", host, diag_last,
                       diagnostics(state, cfg))
        shutil.rmtree(WORK / "diag.bp4")

        phase_device_payload(state)
        check_kernel_compiled()
        ck = phase_checkpoint(state, cfg, WORK / "ckpt", host,
                              steps_per_chunk=STEPS_PER_CHUNK)
        for key in ("ckpt_save_s", "ckpt_restore_s", "chunk_s_live",
                    "chunk_s_restored"):
            emit(key, ck[key])
        emit("COMPRESS_DEVICE_BYTES", ck["COMPRESS_DEVICE_BYTES"])
        emit("darshan_bytes_written_ckpt", ck["POSIX_BYTES_WRITTEN"])
        emit("peak_bytes_in_use", device_peak_bytes(device))

        n = log.count["jit(pic_run_chunk)"]
        emit("compiles_pic_run_chunk", n)
        emit("compile_s_pic_run_chunk", log.seconds["jit(pic_run_chunk)"])
        emit("compile_s_total", sum(log.seconds.values()))
        check(n == 1, f"pic_run_chunk compiled {n} times, not once")
    finally:
        log.close()
        shutil.rmtree(WORK, ignore_errors=True)


def run_four_chips(devices):
    from repro.configs.bit1 import paper_config
    from repro.launch.compile_cache import enable_compile_cache

    emit("compile_cache_dir", enable_compile_cache())
    log = CompileLog()
    shutil.rmtree(WORK, ignore_errors=True)
    WORK.mkdir()
    try:
        out = phase_sharded(paper_config(), WORK / "ckpt4", devices)
        for key, value in out.items():
            emit(key, value)
        for d in devices:
            emit(f"peak_bytes_in_use_{d.id}", device_peak_bytes(d))
        emit("compile_s_total", sum(log.seconds.values()))
    finally:
        log.close()
        shutil.rmtree(WORK, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded checkpoint path")
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    import jax
    if args.chips == 4:
        run_four_chips(devices)
    else:
        run_one_chip(devices[0])
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
