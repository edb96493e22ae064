"""Bytes that a piece of work has to move, computed from shapes. These
are the yardstick of the roofline shares; see PERF.md for the reasoning.
"""
from __future__ import annotations

import math

PIC_SPECIES = 3
# one slot of a species: x (4 B), v (3 x 4 B), w (4 B), alive (4 B)
PIC_SLOT_BYTES = 24


def pic_step_bytes(capacity: int) -> int:
    """Least bytes one PIC step moves: every slot of every species read
    once and written once (positions move every step, and a slot's
    record is rewritten where a particle is born or dies)."""
    return 2 * PIC_SPECIES * capacity * PIC_SLOT_BYTES


def shuffle_bytes(n_bytes: int) -> int:
    """Bytes the byte-shuffle kernel moves for `n_bytes` of input: each
    byte read once and written once."""
    return 2 * n_bytes


def split_axis(shape, parts: int) -> int | None:
    """The first axis of `shape` that `parts` divides, or None."""
    return next((i for i, s in enumerate(shape) if int(s) % parts == 0), None)


def shard_shape(shape, parts: int) -> tuple:
    """`shape` cut into `parts` along its first axis that `parts` divides
    (FSDP/ZeRO-3 sharding of one leaf); scalars are replicated."""
    shape = tuple(int(s) for s in shape)
    i = split_axis(shape, parts)
    if i is not None:
        return shape[:i] + (shape[i] // parts,) + shape[i + 1:]
    if not shape:
        return shape
    raise ValueError(f"no axis of {shape} is divisible by {parts}")


def shard_leaves(leaves: dict, parts: int) -> dict:
    """Name -> (shard shape, dtype) for every leaf of a state."""
    return {name: (shard_shape(spec["shape"], parts), spec["dtype"])
            for name, spec in leaves.items()}


def leaf_bytes(leaves: dict, *, dtype: str | None = None) -> int:
    """Bytes of the leaves of `shard_leaves`, of one dtype if given."""
    size = {"float32": 4, "int32": 4, "bfloat16": 2}
    return sum(math.prod(shape) * size[dt]
               for shape, dt in leaves.values()
               if dtype is None or dt == dtype)
