"""Jit'd public wrapper: pads to kernel tiling, dispatches kernel vs oracle.

On the CPU backend the kernel runs interpret=True (Python-level Pallas
execution) — the TPU path is identical code with interpret=False.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import auto_interpret
from repro.kernels.bitshuffle.kernel import (TILE_N, byte_shuffle_block,
                                             byte_shuffle_tpu,
                                             byte_unshuffle_tpu)
from repro.kernels.bitshuffle.ref import byte_shuffle_ref


def shuffle(data: jax.Array, *, itemsize: int,
            interpret: bool | None = None) -> jax.Array:
    """uint8 [n] -> shuffled uint8 [n]; n padded internally to tile size."""
    interpret = auto_interpret() if interpret is None else interpret
    n = data.shape[0]
    tile_bytes = itemsize * TILE_N
    pad = (-n) % tile_bytes
    x = jnp.pad(data, (0, pad))
    # shuffle the padded [n_items, itemsize] matrix; slicing the first n
    # bytes of the inverse-unshuffled stream restores exactly data, but for
    # the compression pipeline we keep the padded frame (header records n).
    out = byte_shuffle_tpu(x, itemsize=itemsize, interpret=interpret)
    return out, n


def _items(arr, first, n_items: int):
    """The `n_items` row-major items of `arr` that start at flat item
    `first`. Only the rows that hold them are flattened: on a TPU,
    flattening a whole array with a narrow last dim ([C, 3] float32)
    relays it out into 128-lane tiles, 40x its size in temp memory."""
    if arr.ndim <= 1 or arr.size == 0:
        return jax.lax.dynamic_slice(arr.reshape(-1), (first,), (n_items,))
    row = math.prod(arr.shape[1:])
    n_rows = min(arr.shape[0], n_items // row + 2)
    r0 = jnp.clip(first // row, 0, arr.shape[0] - n_rows)
    rows = jax.lax.dynamic_slice_in_dim(arr, r0, n_rows).reshape(-1)
    return jax.lax.dynamic_slice(rows, (first - r0 * row,), (n_items,))


def _bytes(items):
    if items.dtype == jnp.uint8:
        return items
    return jax.lax.bitcast_convert_type(items, jnp.uint8).reshape(-1)


@functools.partial(jax.jit, static_argnames=("n_items",))
def item_bytes(arr: jax.Array, first, *, n_items: int) -> jax.Array:
    """uint8 bytes of `n_items` items of `arr` from flat item `first`."""
    return _bytes(_items(arr, first, n_items))


@functools.partial(jax.jit, static_argnames=("n_items", "interpret"))
def shuffled_items(arr: jax.Array, first, *, n_items: int,
                   interpret: bool) -> jax.Array:
    """`byte_shuffle_block` of the bytes of `n_items` items of `arr` from
    flat item `first`, in one program: the kernel takes the items' byte
    matrix as it is, never a flat byte copy of it (on a TPU that copy is
    a relayout that takes the compiler minutes per shape)."""
    itemsize = jnp.dtype(arr.dtype).itemsize
    return byte_shuffle_block(_bytes(_items(arr, first, n_items)),
                              itemsize=itemsize, interpret=interpret)


def shuffle_block(data: jax.Array, *, itemsize: int,
                  interpret: bool | None = None) -> jax.Array:
    """Shuffle exactly one codec block on-device: uint8 [n] -> uint8 [n]
    with n % itemsize == 0 and NO padding — output is bit-identical to the
    host `compression.byte_shuffle` on the same bytes. One pallas grid
    point per call (the per-codec-block shape the write path uses)."""
    interpret = auto_interpret() if interpret is None else interpret
    if data.shape[0] % itemsize:
        raise ValueError(
            f"shuffle_block needs len % itemsize == 0, got "
            f"{data.shape[0]} % {itemsize}")
    return byte_shuffle_block(data, itemsize=itemsize, interpret=interpret)


def unshuffle(data: jax.Array, n: int, *, itemsize: int,
              interpret: bool | None = None) -> jax.Array:
    interpret = auto_interpret() if interpret is None else interpret
    out = byte_unshuffle_tpu(data, itemsize=itemsize, interpret=interpret)
    return out[:n]
