"""One chip's shard of a training state through `CheckpointManager`:
a closed loop of save -> wait -> restore_latest -> block_until_ready.

Set-up makes the state on the device from the seed in one jitted call
(a mid-training state: parameters and both Adam moments non-zero at
plausible scales), makes one cycle to compile or load the shuffle
programs and to spawn the writer plane, then hands the same manager to
the window. Every save holds another `step`, so a stale checkpoint
shows.

The traffic's `save_chips` and `restore_chips` (1 when absent) are the
layouts: the state is saved from `save_chips` devices and restored onto
`restore_chips`, each leaf split along its first axis that the count
divides (`layout`), as a job resumed under another layout does."""
from __future__ import annotations

import functools
import random
import time

import numpy as np

from benchmarks.chip import counts
from benchmarks.chip.reference import ckpt as ref


def floating(dtype) -> bool:
    import jax.numpy as jnp
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def layout(leaves: dict, devices) -> dict:
    """Name -> sharding of every leaf over `devices`: the one device, or a
    1-D mesh on which each leaf is split along its first axis that the
    device count divides (`counts.split_axis`), replicated where none
    does."""
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)
    if len(devices) == 1:
        return {n: SingleDeviceSharding(devices[0]) for n in leaves}
    mesh = Mesh(np.array(devices), ("chips",))
    out = {}
    for name, (shape, _) in leaves.items():
        spec = [None] * len(shape)
        axis = counts.split_axis(shape, len(devices))
        if axis is not None:
            spec[axis] = "chips"
        out[name] = NamedSharding(mesh, PartitionSpec(*spec))
    return out


def make_state(leaves: dict, init: dict, key, shardings: dict):
    """Name -> device array for every shard leaf, placed as `shardings`
    says, in one jitted call. Floating leaves are drawn in their own
    dtype; other leaves are zeros."""
    import jax
    import jax.numpy as jnp

    names = sorted(leaves)

    @functools.partial(jax.jit, out_shardings=shardings)
    def make(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shape, dt = leaves[name]
            if not floating(dt):
                out[name] = jnp.zeros(shape, dt)
                continue
            z = jax.random.normal(k, shape, dt)
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
        from repro.ckpt.manager import CheckpointManager
        from repro.core.bp_engine import EngineConfig
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.leaves = counts.shard_leaves(cfg["leaves"], cfg["fsdp"])
        devices = jax.devices()
        n_save = traffic.get("save_chips", 1)
        n_restore = traffic.get("restore_chips", 1)
        if max(n_save, n_restore) > len(devices):
            raise ValueError(f"the traffic asks for {max(n_save, n_restore)} "
                             f"devices, JAX has {len(devices)}")
        self.save_layout = layout(self.leaves, devices[:n_save])
        self.shardings = layout(self.leaves, devices[:n_restore])
        self.state = jax.block_until_ready(make_state(
            self.leaves, cfg["init"], chip_key, self.save_layout))
        # what a restore is compared with, on the restore's layout: the
        # state itself, or a copy made once, outside every timed interval
        self.expect = self.state
        if self.shardings != self.save_layout:
            self.expect = jax.block_until_ready(
                jax.device_put(self.state, self.shardings))
        self.like = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in self.state.items()}
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

    def _with_step(self, state: dict, shardings: dict, step: int) -> dict:
        import jax
        return dict(state, step=jax.device_put(np.int32(step),
                                               shardings["step"]))

    def _cycle(self) -> tuple[float, float]:
        import jax
        self.step += 1
        st = self._with_step(self.state, self.save_layout, self.step)
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
            expect = self._with_step(self.expect, self.shardings, self.step)
            diff = jax.device_get(self._equal(got[0], expect))
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
        # a split leaf's boxes tile it and a replicated one is written
        # once, so a save shuffles each float32 leaf's bytes once
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
        (counted as the window ran), and the stored chunks of a sample of
        floating leaves, drawn from the seed: together they have to cover
        the leaf once, and each chunk's blocks have to be the reference
        shuffle of its own box of the leaf."""
        from repro.ckpt.checkpoint import checkpoint_path
        from repro.core.bp_engine import BpReader
        import jax
        lim = self.cfg["limits"]
        names = sorted(n for n, (_, dt) in self.leaves.items() if floating(dt))
        sample = random.Random(self.seed).sample(names, lim["payload_leaves"])
        host = {n: np.asarray(jax.device_get(self.saved[n])) for n in sample}
        self.state = self.saved = self.expect = None
        self.manager.close()
        off = 0
        with BpReader(checkpoint_path(self.manager.dir, self.step)) as reader:
            for n in sample:
                try:
                    chunks = list(reader.iter_chunks(self.step, f"state/{n}"))
                except KeyError:                 # the leaf was not saved
                    chunks = []
                leaf = host[n].reshape(host[n].shape or (1,))
                if not ref.covers_once([(c.offset, c.extent) for c in chunks],
                                       leaf.shape):
                    off += 1
                    continue
                for ch in chunks:
                    box = tuple(slice(o, o + e)
                                for o, e in zip(ch.offset, ch.extent))
                    payload = reader._read_payload(ch.agg, ch.file_offset,
                                                   ch.nbytes)
                    off += ref.blocks_off(payload, leaf[box].tobytes(),
                                          leaf.dtype.itemsize)
        checks = {"restore_off": {"value": self.restore_off, "limit": 0},
                  "payload_off": {"value": off, "limit": 0}}
        return checks, min(self.restore_off, self.cycles)

    def close(self):
        self.manager.close()


def jnp_bits_differ(a, b):
    """Elements of two arrays of one dtype whose bits differ."""
    import jax
    import jax.numpy as jnp
    bits = {4: jnp.uint32, 2: jnp.uint16}.get(a.dtype.itemsize)
    if bits is not None:
        a = jax.lax.bitcast_convert_type(a, bits)
        b = jax.lax.bitcast_convert_type(b, bits)
    return jnp.sum(a != b)
