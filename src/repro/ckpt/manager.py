"""CheckpointManager: async double-buffered writes, retention, auto-restart.

Fault-tolerance contract (DESIGN.md §6):
  * training never blocks on storage — save() snapshots the state to host
    (device->host copy) and hands it to a writer thread; the checkpoint
    write (through the JBP async pipeline when `engine_async`) then
    OVERLAPS the next train step, and `wait()` is the barrier that
    re-serialises producer and writer,
  * a checkpoint becomes visible only after its atomic rename; a crash
    mid-write leaves a .tmp the next run ignores,
  * restore_latest() walks checkpoints newest-first and returns the first
    one whose md.idx validates (torn/corrupt ones are skipped),
  * keep_n retention runs behind the durability barrier: old checkpoints
    are evicted only AFTER the newer one's sealed md.idx + rename — the
    same wait()-before-eviction ordering the writer job enforces in-line.
"""
from __future__ import annotations

import pathlib
import shutil
import threading
import time
from typing import Optional

import jax
import numpy as np

from repro.ckpt import checkpoint as CK
from repro.core.bp_engine import EngineConfig
from repro.core.dxt import TRACER


class CheckpointManager:
    def __init__(self, directory, *, every: int = 100, keep_n: int = 3,
                 n_io_ranks: int = 8,
                 engine_config: EngineConfig = EngineConfig(),
                 async_write: bool = True, engine_async: bool = False,
                 parallel_io: int = 0, transport: str = "shm",
                 device_compress: bool = False):
        # async_write is what hides checkpoint I/O behind the next train
        # step (the writer thread). engine_async additionally routes the
        # write through AsyncBpWriter — correctness-neutral (checkpoints
        # force fsync_policy="step", a blocking seal), useful when shared
        # pipeline profiling is wanted; off by default. parallel_io=W
        # routes the write through W real writer processes instead
        # (repro.core.parallel_engine) — compression and subfile appends
        # leave the training process entirely; takes precedence over
        # engine_async. The W processes are a PERSISTENT WriterPlane:
        # spawned lazily on the first save and retargeted per checkpoint,
        # so the spawn cost is paid once per run, not once per `every`
        # steps; with transport="shm" (default) the plane's per-worker
        # shared-memory rings stay mapped across saves too, so every save
        # ships leaf chunks by memcpy + header instead of pickling the
        # whole state down worker queues. `close()` tears the plane down
        # and unlinks the rings (a finalizer covers abnormal exits).
        # device_compress=True keeps device leaves ON-CHIP at save():
        # jax.Arrays are immutable, so the snapshot needs no host copy,
        # and save_checkpoint byte-shuffles each shard on the accelerator
        # before the writer handoff (workers then skip the shuffle).
        self.dir = pathlib.Path(str(directory))
        self.dir.mkdir(parents=True, exist_ok=True)
        self.every = every
        self.keep_n = keep_n
        self.n_io_ranks = n_io_ranks
        self.engine_config = engine_config
        self.async_write = async_write
        self.engine_async = engine_async
        self.parallel_io = int(parallel_io)
        self.transport = transport
        self.device_compress = bool(device_compress)
        self._plane = None                       # lazy persistent write plane
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self.saved_steps: list[int] = []
        # overlap accounting: how long save()/wait() actually stalled the
        # producer vs how long the background writes took
        self.stats = {"saves": 0, "blocked_s": 0.0, "write_s": 0.0}

    # ----------------------------------------------------------------- save
    def should_save(self, step: int) -> bool:
        return step > 0 and step % self.every == 0

    def wait(self):
        """Barrier: the in-flight checkpoint (if any) is durable on return.
        Must run before eviction and before the manager is torn down."""
        t0 = time.perf_counter()
        if self._thread is not None:
            self._thread.join()
            self._thread = None
            self.stats["blocked_s"] += time.perf_counter() - t0
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def overlap_fraction(self) -> float:
        """Share of checkpoint write time hidden behind training compute."""
        w = self.stats["write_s"]
        return max(0.0, 1.0 - self.stats["blocked_s"] / w) if w > 0 else 0.0

    def _writer_plane(self):
        """The persistent parallel write plane, spawned on first use and
        respawned if its workers died (e.g. a prior save crashed them)."""
        if not self.parallel_io:
            return None
        if self._plane is not None and not self._plane.alive():
            self._plane.shutdown()
            self._plane = None
        if self._plane is None:
            from repro.core.parallel_engine import WriterPlane
            self._plane = WriterPlane(self.parallel_io,
                                      transport=self.transport)
        return self._plane

    def save(self, state, step: int, *, force: bool = False):
        if not force and not self.should_save(step):
            return False
        with TRACER.span("ckpt_save", path=str(self.dir)):
            return self._save(state, step)

    def _save(self, state, step: int) -> bool:
        self.wait()                                  # one write in flight max

        def snap(x):
            # with device_compress a jax.Array stays on-chip: it is
            # immutable, so the producer can keep training on it while
            # the writer shuffles/compresses this very buffer
            from repro.core import compression as C
            if self.device_compress and C.is_device_array(x):
                return x
            return np.asarray(jax.device_get(x))

        host_state = jax.tree_util.tree_map(snap, state)

        def job():
            try:
                with TRACER.span("ckpt_write", path=str(self.dir)):
                    t0 = time.perf_counter()
                    CK.save_checkpoint(self.dir, host_state, step,
                                       n_io_ranks=self.n_io_ranks,
                                       engine_config=self.engine_config,
                                       async_io=(self.engine_async
                                                 and not self.parallel_io),
                                       parallel_io=self.parallel_io,
                                       writer_plane=self._writer_plane(),
                                       device_compress=self.device_compress)
                    self.stats["write_s"] += time.perf_counter() - t0
                    self.saved_steps.append(step)
                    # durability barrier passed (sealed md.idx + rename
                    # above): only now may older checkpoints be evicted
                    self._retain()
            except BaseException as e:               # noqa: BLE001
                self._error = e

        self.stats["saves"] += 1
        if self.async_write:
            self._thread = threading.Thread(target=job, daemon=True)
            self._thread.start()
        else:
            t0 = time.perf_counter()
            job()                    # inline write: all of it blocks training
            self.stats["blocked_s"] += time.perf_counter() - t0
        return True

    def _retain(self):
        steps = CK.list_checkpoints(self.dir)
        for s in steps[:-self.keep_n] if self.keep_n else []:
            shutil.rmtree(CK.checkpoint_path(self.dir, s), ignore_errors=True)
        for tmp in self.dir.glob("*.bp4.tmp"):       # torn writes
            shutil.rmtree(tmp, ignore_errors=True)

    def close(self):
        """Drain the in-flight save and tear down the persistent writer
        plane (if any). The manager stays usable — a later save respawns
        the plane lazily."""
        try:
            self.wait()
        finally:
            plane, self._plane = self._plane, None
            if plane is not None:
                plane.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()

    # -------------------------------------------------------------- restore
    def restore_latest(self, like, shardings=None, *, parallel: int = 0):
        """Newest valid checkpoint, or None if there is none. `parallel=N`
        fans each leaf's chunk reads over a ReaderPool."""
        with TRACER.span("restore", path=str(self.dir)):
            self.wait()
            steps = CK.list_checkpoints(self.dir)
            for step in reversed(steps):
                try:
                    if shardings is not None:
                        return CK.restore_sharded(self.dir, like, shardings,
                                                  step=step,
                                                  parallel=parallel)
                    return CK.restore_checkpoint(self.dir, like, step=step,
                                                 parallel=parallel)
                except Exception:                    # noqa: BLE001
                    continue
            return None
