"""Training-state checkpoint/restore through the JBP (openPMD/BP4) engine.

The checkpoint is one openPMD-style step whose variables are the flattened
TrainState leaves ("params/stack/layers/attn/wq/w", ...). Each leaf is
written as chunks by logical I/O rank — from real jax.Array shards when the
array is sharded, else by row-split — so N ranks -> M aggregator subfiles
exactly as the paper's BIT1 checkpoints (.dmp) map onto BP4.

Restore supports ELASTIC RE-SHARDING: `restore_sharded` reads, per device of
the *new* mesh, exactly the box that shard needs (BpReader box selection),
so a job restarted at a different scale never reads the full state.
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import threading
from typing import Any, Optional

import jax
import numpy as np

from repro.core import compression as C
from repro.core.bp_engine import BpReader, BpWriter, EngineConfig
from repro.core.darshan import CTR, MONITOR
from repro.core.dxt import TRACER

SEP = "/"


def _to_storage(arr: np.ndarray) -> np.ndarray:
    """bfloat16 (ml_dtypes) round-trips through raw uint16 storage."""
    if arr.dtype.itemsize == 2 and "bfloat16" in str(arr.dtype):
        return arr.view(np.uint16)
    return arr


def _from_storage(arr: np.ndarray, target_dtype) -> np.ndarray:
    if arr.dtype == np.uint16 and "bfloat16" in str(np.dtype(target_dtype)):
        import ml_dtypes
        return arr.view(ml_dtypes.bfloat16)
    return arr.astype(target_dtype)


def flatten_state(state) -> dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        name = SEP.join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        flat[name] = leaf
    return flat


def _leaf_chunks(arr: np.ndarray, n_ranks: int):
    """(rank, offset, chunk) row-split of a host array (scalars -> [1])."""
    if arr.ndim == 0:
        yield 0, (0,), arr.reshape(1)
        return
    n = min(n_ranks, arr.shape[0]) or 1
    bounds = np.linspace(0, arr.shape[0], n + 1).astype(int)
    for r in range(n):
        lo, hi = int(bounds[r]), int(bounds[r + 1])
        if hi > lo:
            yield r, (lo,) + (0,) * (arr.ndim - 1), arr[lo:hi]


def _distinct_boxes(leaf) -> list:
    """One addressable shard per distinct box of a device array, in offset
    order: the replicas of a box are written once. Empty for host leaves."""
    boxes = {}
    for sh in getattr(leaf, "addressable_shards", ()):
        boxes.setdefault(tuple(sl.start or 0 for sl in sh.index), sh)
    return [boxes[k] for k in sorted(boxes)]


def save_checkpoint(directory, state, step: int, *, n_io_ranks: int = 8,
                    engine_config: EngineConfig = EngineConfig(),
                    extra_attrs: Optional[dict] = None,
                    async_io: bool = False,
                    parallel_io: int = 0,
                    writer_plane=None,
                    transport: str = "shm",
                    device_compress: bool = False) -> pathlib.Path:
    """Atomic checkpoint write: <dir>/step_<N>.bp4 (.tmp + rename).

    With `async_io` the write goes through the AsyncBpWriter pipeline;
    fsync_policy is still forced to "step", which the async engine honours
    with a BLOCKING seal — so by the time the .tmp is renamed the step's
    md.idx record is durable either way. `parallel_io=W` instead writes
    through W real writer processes (two-phase commit; the md.idx seal and
    every subfile/shard fsync precede the rename), with chunk bytes moved
    over per-worker shared-memory rings (`transport="shm"`, the default)
    rather than pickled down queues. `writer_plane` (a
    `repro.core.parallel_engine.WriterPlane`) supplies ALREADY-RUNNING
    writer processes for the parallel path — the spawn cost is the plane
    owner's, paid once per run instead of once per save, and the plane's
    rings stay mapped across saves (the plane inherits its own transport;
    `transport` applies to the spawn-per-save path).

    `device_compress=True` byte-shuffles device leaves ON-CHIP
    (repro.core.compression.device_precondition) before the writer hand-
    off — with parallel_io the workers receive pre-shuffled bytes over
    the shm rings and pay only the LZ stage. A sharded leaf is written
    one box per distinct shard (replicas once). Host leaves, scalars and
    bfloat16 (raw uint16 storage) keep the host path."""
    directory = pathlib.Path(str(directory))
    directory.mkdir(parents=True, exist_ok=True)
    final = directory / f"step_{step:08d}.bp4"
    tmp = directory / f"step_{step:08d}.bp4.tmp"
    if tmp.exists():
        shutil.rmtree(tmp)

    flat = flatten_state(state)
    import dataclasses as _dc
    cfg = _dc.replace(engine_config, fsync_policy="step",
                      device_compress=(device_compress
                                       or engine_config.device_compress))
    use_dev = cfg.device_compress and C.codec_wants_device(cfg.codec)
    if parallel_io or writer_plane is not None:
        from repro.core.parallel_engine import ParallelBpWriter
        w = ParallelBpWriter(tmp, n_io_ranks, cfg,
                             n_writers=parallel_io or None,
                             plane=writer_plane, transport=transport)
    elif async_io:
        from repro.core.async_engine import AsyncBpWriter
        w = AsyncBpWriter(tmp, n_io_ranks, cfg)
    else:
        w = BpWriter(tmp, n_io_ranks, cfg)
    try:
        w.begin_step(step)
        w.set_attribute("checkpoint/step", step)
        w.set_attribute("checkpoint/n_leaves", len(flat))
        for k, v in (extra_attrs or {}).items():
            w.set_attribute(k, v)
        for name, leaf in flat.items():
            dev_ok = (use_dev and "bfloat16" not in str(leaf.dtype)
                      and getattr(leaf, "ndim", 0) > 0)
            boxes = _distinct_boxes(leaf)
            if len(boxes) == 1:
                # replicated, or on one device: write one copy as a leaf
                leaf = boxes[0].data
            elif boxes:
                gshape = tuple(leaf.shape)
                for i, sh in enumerate(boxes):
                    off = tuple(sl.start or 0 for sl in sh.index)
                    # writer rank from the box's position, not the device
                    # id: ids need not be below n_io_ranks
                    rank = i * n_io_ranks // len(boxes)
                    if dev_ok:
                        # on-chip bitshuffle per shard BEFORE the writer
                        # handoff: downstream (threads or shm workers)
                        # only runs the LZ stage on pre-shuffled bytes
                        chunk = C.device_precondition(
                            sh.data, block=cfg.compression_block)
                        MONITOR.record(0, str(tmp),
                                       CTR.COMPRESS_DEVICE_BYTES,
                                       inc=float(chunk.device_bytes))
                        w.put(f"state/{name}", chunk, global_shape=gshape,
                              offset=off, rank=rank)
                    else:
                        w.put(f"state/{name}", _to_storage(np.asarray(sh.data)),
                              global_shape=gshape, offset=off, rank=rank)
                continue
            if dev_ok and C.is_device_array(leaf):
                # single-shard device leaf: keep it on-device — the engine
                # preconditions it itself (cfg.device_compress is set)
                w.put(f"state/{name}", leaf, global_shape=tuple(leaf.shape),
                      offset=(0,) * leaf.ndim, rank=0)
            else:
                host = _to_storage(np.asarray(jax.device_get(leaf)))
                gshape = host.shape if host.ndim else (1,)
                for r, off, chunk in _leaf_chunks(host, n_io_ranks):
                    w.put(f"state/{name}", chunk, global_shape=gshape,
                          offset=off, rank=r)
        w.end_step()
    except BaseException:
        # a failed save must not leak the writer thread / open md handles;
        # the ORIGINAL error is what propagates
        try:
            w.close()
        except BaseException:        # noqa: BLE001
            pass
        raise
    w.close()
    if final.exists():
        shutil.rmtree(final)
    os.rename(tmp, final)
    (directory / "latest.txt").write_text(str(step))
    return final


def list_checkpoints(directory) -> list[int]:
    directory = pathlib.Path(str(directory))
    out = []
    for p in sorted(directory.glob("step_*.bp4")):
        try:
            with BpReader(p) as reader:
                if reader.valid_steps():
                    out.append(int(p.name[5:13]))
        except Exception:       # noqa: BLE001 — corrupt checkpoint: skip
            continue
    return sorted(out)


def checkpoint_path(directory, step: int) -> pathlib.Path:
    return pathlib.Path(str(directory)) / f"step_{step:08d}.bp4"


def restore_checkpoint(directory, like, step: Optional[int] = None,
                       *, parallel: int = 0):
    """Restore into the structure of `like` (pytree of arrays or
    ShapeDtypeStructs). Full-array read (single-host path). `parallel=N`
    fans multi-chunk leaf reads over a ReaderPool; the context manager
    guarantees the reader (pool + subfile handles) is released even when
    a leaf is missing or corrupt mid-restore."""
    directory = pathlib.Path(str(directory))
    steps = list_checkpoints(directory)
    if not steps:
        raise FileNotFoundError(f"no valid checkpoints under {directory}")
    step = step if step is not None else steps[-1]
    flat = flatten_state(like)
    out = {}
    with BpReader(checkpoint_path(directory, step),
                  parallel=parallel) as reader:
        for name, leaf in flat.items():
            arr = reader.read_var(step, f"state/{name}")
            out[name] = _from_storage(arr, leaf.dtype).reshape(leaf.shape)
    return unflatten_like(like, out), step


def restore_sharded(directory, like, shardings, step: Optional[int] = None,
                    *, parallel: int = 0):
    """Elastic restore: `like` + `shardings` describe the NEW mesh layout;
    every device shard reads exactly its box from the chunk table."""
    directory = pathlib.Path(str(directory))
    steps = list_checkpoints(directory)
    if not steps:
        raise FileNotFoundError(f"no valid checkpoints under {directory}")
    step = step if step is not None else steps[-1]
    flat_like = flatten_state(like)
    flat_sh = flatten_state(shardings)
    out = {}
    with BpReader(checkpoint_path(directory, step),
                  parallel=parallel) as reader:
        for name, leaf in flat_like.items():
            sh = flat_sh[name]
            var = f"state/{name}"

            def fetch(idx, _var=var, _leaf=leaf):
                off = tuple((sl.start or 0) for sl in idx)
                ext = tuple((sl.stop if sl.stop is not None else s) -
                            (sl.start or 0) for sl, s in zip(idx, _leaf.shape))
                # read and decode on the host; the copy to the device is
                # the caller's
                with TRACER.span("restore_fetch", path=_var):
                    a = reader.read_var(step, _var, off, ext)
                    return _from_storage(a, _leaf.dtype)

            if leaf.ndim == 0:
                with TRACER.span("restore_fetch", path=var):
                    arr = _from_storage(reader.read_var(step, var),
                                        leaf.dtype).reshape(())
                out[name] = jax.device_put(arr, sh)
            else:
                out[name] = jax.make_array_from_callback(leaf.shape, sh, fetch)
    return unflatten_like(like, out), step


def unflatten_like(like, flat: dict):
    treedef = jax.tree_util.tree_structure(like)
    paths = [SEP.join(str(getattr(k, "key", getattr(k, "idx", k)))
                      for k in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(like)[0]]
    return jax.tree_util.tree_unflatten(treedef, [flat[p] for p in paths])
