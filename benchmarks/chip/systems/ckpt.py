"""One chip's shard of a training state through `CheckpointManager`:
a closed loop of save -> wait -> restore_latest -> block_until_ready.

Set-up makes the state on the device from the seed in one jitted call
(a mid-training state: parameters and both Adam moments non-zero at
plausible scales), makes one cycle to compile or load the shuffle
programs and to spawn the writer plane, then hands the same manager to
the window. Every save holds another `step`, so a stale checkpoint
shows."""
from __future__ import annotations

import random
import time

import numpy as np

from benchmarks.chip import counts
from benchmarks.chip.reference import ckpt as ref


def make_state(leaves: dict, init: dict, key):
    """Name -> device array for every shard leaf, in one jitted call."""
    import jax
    import jax.numpy as jnp

    names = sorted(leaves)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape, dt = leaves[name]
            if dt != "float32":
                out[name] = jnp.zeros(shape, dt)
                continue
            z = jax.random.normal(k, shape, jnp.float32)
            if name.startswith("opt/v/"):
                out[name] = jnp.square(z * init["grad_std"])
            elif name.startswith("opt/m/"):
                out[name] = z * init["grad_std"]
            else:
                out[name] = z * init["param_std"]
        return out

    return make(key)


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int, work, *,
                 chip_key):
        import jax
        from jax.sharding import SingleDeviceSharding
        from repro.ckpt.manager import CheckpointManager
        from repro.core.bp_engine import EngineConfig
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.leaves = counts.shard_leaves(cfg["leaves"], cfg["fsdp"])
        self.state = jax.block_until_ready(
            make_state(self.leaves, cfg["init"], chip_key))
        device = jax.devices()[0]
        self.like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in self.state.items()}
        self.shardings = {k: SingleDeviceSharding(device) for k in self.state}
        self.manager = CheckpointManager(
            work / "ckpt", keep_n=traffic["keep_n"],
            n_io_ranks=traffic["n_io_ranks"],
            engine_config=EngineConfig(codec=traffic["codec"]),
            parallel_io=traffic["parallel_io"],
            device_compress=traffic["device_compress"])
        self._equal = jax.jit(lambda a, b: {
            k: jnp_bits_differ(a[k], b[k]) for k in a})
        self.step = 0
        self.restore_off = 0
        self.saved = None
        self._cycle()          # warm-up: the window's calls, and checked alike
        self.cycles = 0
        self.counters = {}

    def _with_step(self, step: int) -> dict:
        import jax.numpy as jnp
        st = dict(self.state)
        st["step"] = jnp.asarray(step, jnp.int32)
        return st

    def _cycle(self) -> tuple[float, float]:
        import jax
        self.step += 1
        st = self._with_step(self.step)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.save"):
            self.manager.save(st, self.step, force=True)
        with jax.profiler.TraceAnnotation("bench.wait"):
            self.manager.wait()
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.restore_latest"):
            got = self.manager.restore_latest(self.like, self.shardings)
            if got is not None:
                jax.block_until_ready(got[0])
        t2 = time.perf_counter()
        if got is None or got[1] != self.step:
            self.restore_off += len(st)
        else:
            diff = jax.device_get(self._equal(got[0], st))
            self.restore_off += sum(int(v) > 0 for v in diff.values())
        self.saved = st
        return t1 - t0, t2 - t1

    def window(self, seconds: float) -> dict:
        stats0 = dict(self.manager.stats)
        save_s = restore_s = 0.0
        t0 = time.perf_counter()
        while True:                 # whole cycles, the last one past the end
            s, r = self._cycle()
            save_s += s
            restore_s += r
            self.cycles += 1
            if time.perf_counter() - t0 >= seconds:
                break
        wall = time.perf_counter() - t0
        stats = self.manager.stats
        self.counters = {
            "cycles": self.cycles, "saves": stats["saves"] - stats0["saves"],
            "blocked_s": stats["blocked_s"] - stats0["blocked_s"],
            "shuffled_bytes": self.cycles * counts.leaf_bytes(
                self.leaves, dtype="float32"),
            "wall_s": wall}
        return {"ckpt_save_s": save_s / self.cycles,
                "ckpt_restore_s": restore_s / self.cycles}

    @property
    def attempted(self) -> int:
        return self.cycles

    def check(self) -> tuple[dict, int]:
        """Every restore of the window against what was saved, bit for bit
        (counted as the window ran), and the stored blocks of a sample of
        leaves, drawn from the seed, against the reference shuffle."""
        from repro.ckpt.checkpoint import checkpoint_path
        from repro.core.bp_engine import BpReader
        import jax
        lim = self.cfg["limits"]
        names = sorted(n for n, (_, dt) in self.leaves.items()
                       if dt == "float32")
        sample = random.Random(self.seed).sample(names, lim["payload_leaves"])
        host = {n: np.asarray(jax.device_get(self.saved[n])) for n in sample}
        self.state = self.saved = None
        self.manager.close()
        off = 0
        with BpReader(checkpoint_path(self.manager.dir, self.step)) as reader:
            for n in sample:
                try:
                    chunks = list(reader.iter_chunks(self.step, f"state/{n}"))
                except KeyError:                 # the leaf was not saved
                    chunks = []
                if len(chunks) != 1:
                    off += 1
                    continue
                ch = chunks[0]
                payload = reader._read_payload(ch.agg, ch.file_offset,
                                               ch.nbytes)
                off += ref.blocks_off(payload, host[n].tobytes(),
                                      host[n].dtype.itemsize)
        checks = {"restore_off": {"value": self.restore_off, "limit": 0},
                  "payload_off": {"value": off, "limit": 0}}
        return checks, min(self.restore_off, self.cycles)

    def close(self):
        self.manager.close()


def jnp_bits_differ(a, b):
    """Elements of two arrays of one dtype whose bits differ."""
    import jax
    import jax.numpy as jnp
    if a.dtype.itemsize == 4:
        a = jax.lax.bitcast_convert_type(a, jnp.uint32)
        b = jax.lax.bitcast_convert_type(b, jnp.uint32)
    return jnp.sum(a != b)
