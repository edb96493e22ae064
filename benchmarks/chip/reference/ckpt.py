"""Plain reference of what a checkpoint must hold: the saved leaves bit
for bit, and each stored block a byte shuffle of the leaf's bytes
(blosc's layout: the k-th byte of every item together), compressed by
deflate. Written from the format's description; imports nothing of the
program under test."""
from __future__ import annotations

import struct
import zlib

import numpy as np

# a stored block: magic, codec id, item size, flags, raw bytes, stored bytes
BLOCK = struct.Struct("<4sBBHII")
MAGIC = b"JBPC"
CODEC_NONE, CODEC_BLOSC = 0, 1
FLAG_PRESHUFFLED = 0x1


def byte_shuffle(raw: bytes, itemsize: int) -> bytes:
    a = np.frombuffer(raw, np.uint8)
    return a.reshape(-1, itemsize).T.tobytes()


def blocks_off(payload: bytes, leaf_bytes: bytes, itemsize: int) -> int:
    """Stored blocks of one chunk that are not the shuffle of the leaf's
    bytes they cover: a block that does not parse, is of another codec,
    or decodes to other bytes. The chunk must cover `leaf_bytes` whole."""
    off = pos = done = 0
    while pos < len(payload):
        if pos + BLOCK.size > len(payload):
            return off + 1
        magic, codec, isz, flags, raw, stored = BLOCK.unpack_from(payload, pos)
        body = payload[pos + BLOCK.size:pos + BLOCK.size + stored]
        pos += BLOCK.size + stored
        want = byte_shuffle(leaf_bytes[done:done + raw], itemsize)
        done += raw
        if magic != MAGIC or isz != itemsize or len(body) != stored:
            off += 1
        elif codec == CODEC_BLOSC:
            try:
                off += zlib.decompress(body) != want
            except zlib.error:
                off += 1
        elif codec == CODEC_NONE and flags & FLAG_PRESHUFFLED:
            off += body != want
        else:
            off += 1
    return off + (done != len(leaf_bytes))


def covers_once(boxes, shape) -> bool:
    """Whether the (offset, extent) boxes tile an array of `shape`: each
    inside it, no two overlapping, and together as many items as it has."""
    def inside(off, ext):
        return (len(off) == len(ext) == len(shape)
                and all(0 <= o and e > 0 and o + e <= s
                        for o, e, s in zip(off, ext, shape)))

    def overlap(a, b):
        return all(oa < ob + eb and ob < oa + ea
                   for oa, ea, ob, eb in zip(*a, *b))

    boxes = [(tuple(o), tuple(e)) for o, e in boxes]
    return (all(inside(o, e) for o, e in boxes)
            and sum(int(np.prod(e)) for _, e in boxes) == int(np.prod(shape))
            and not any(overlap(a, b) for i, a in enumerate(boxes)
                        for b in boxes[i + 1:]))
