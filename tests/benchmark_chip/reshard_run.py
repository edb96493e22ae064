"""Whole runs of `phi3_fsdp64.reshard_4to1` on four virtual CPU devices,
with the timed path sound or broken underneath, at the tests' small
sizes. JAX has to see the four devices from its start, so the tests run
this in a process of its own; it prints one JSON line per run:

    python3 tests/benchmark_chip/reshard_run.py WORKDIR sound lossy_codec:17 ...

Each argument names a fault, or `sound`, and after a colon a seed. The
control, `lossy_codec`, is the program's own lossy codec in place of the
lossless one.
"""
import json
import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from faults import SEED, small_run  # noqa: E402  (puts the root on the path)
from benchmarks.chip.control import LOSSY  # noqa: E402
from repro.ckpt import checkpoint  # noqa: E402
from repro.core import compression  # noqa: E402
from repro.core.bp_engine import BpReader  # noqa: E402
from repro.core.parallel_engine import ParallelBpWriter  # noqa: E402

CELL = "phi3_fsdp64.reshard_4to1"


def sound(mp):
    pass


def first_chunk_only(mp):
    """A restore that reads only the first stored chunk of each leaf."""
    class FirstChunk(BpReader):
        def iter_chunks(self, step, name):
            yield from list(super().iter_chunks(step, name))[:1]
    mp.setattr(checkpoint, "BpReader", FirstChunk)


def altered_last_shard(mp):
    """One byte of each block that the last chip's shuffle hands on
    flipped."""
    real, last = compression.device_precondition, jax.devices()[3]

    def precondition(arr, **kw):
        chunk = real(arr, **kw)
        if arr.devices() == {last}:
            chunk.data = chunk.data.copy()
            chunk.data[0] ^= np.uint8(1)
        return chunk
    mp.setattr(compression, "device_precondition", precondition)


def device0_box_only(mp):
    """A save that writes only the box at the origin, device 0's: the
    other chips' boxes are dropped."""
    real = ParallelBpWriter.put

    def put(self, name, array, *, offset, **kw):
        if not any(offset):
            real(self, name, array, offset=offset, **kw)
    mp.setattr(ParallelBpWriter, "put", put)


def lossy_codec(mp):
    return {"codec": LOSSY}


FAULTS = {f.__name__: f for f in (sound, first_chunk_only, altered_last_shard,
                                  device0_box_only, lossy_codec)}


def main(argv):
    work = pathlib.Path(argv[0])
    for arg in argv[1:]:
        name, _, seed = arg.partition(":")
        with pytest.MonkeyPatch.context() as mp:
            traffic = FAULTS[name](mp)
            result = small_run(CELL, work / arg.replace(":", "_"),
                               traffic=traffic, seed=int(seed or SEED))
        print(json.dumps({"run": arg, "result": result}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
