"""Codec unit + property tests (blosc-style shuffle+LZ, bzip2, zlib, none)."""
import numpy as np
import pytest
from _propcheck import given, settings, strategies as st

from repro.core import compression as C

CODECS = ["none", "blosc", "bzip2", "zlib"]


@pytest.mark.parametrize("codec", CODECS)
@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32, np.uint8])
def test_array_roundtrip(codec, dtype):
    rng = np.random.default_rng(0)
    arr = (rng.normal(size=(257, 33)) * 100).astype(dtype)
    buf = C.array_payload(arr, codec)
    back = C.payload_to_array(buf, dtype, arr.shape)
    np.testing.assert_array_equal(back, arr)


@pytest.mark.parametrize("codec", CODECS)
def test_multi_block_roundtrip(codec):
    rng = np.random.default_rng(1)
    arr = rng.normal(size=(300_000,)).astype(np.float32)
    buf = C.array_payload(arr, codec, block=64 * 1024)
    back = C.payload_to_array(buf, np.float32, arr.shape)
    np.testing.assert_array_equal(back, arr)


def test_shuffle_improves_float_compression():
    """The Blosc thesis: byte shuffle makes smooth floats compress better."""
    import zlib
    x = (np.linspace(0, 1, 100_000).astype(np.float32) +
         np.random.default_rng(0).normal(scale=1e-4, size=100_000)
         .astype(np.float32))
    raw = x.tobytes()
    plain = len(zlib.compress(raw, 1))
    shuf = len(zlib.compress(C.byte_shuffle(raw, 4), 1))
    assert shuf < plain * 0.9, (shuf, plain)


def test_incompressible_stored_raw():
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
    buf = C.compress(data, "bzip2")
    assert len(buf) <= len(data) + 2 * C.HEADER.size
    assert C.decompress(buf) == data


@settings(max_examples=40, deadline=None)
@given(data=st.binary(min_size=0, max_size=5000),
       codec=st.sampled_from(CODECS),
       itemsize=st.sampled_from([1, 2, 4, 8]),
       block=st.integers(min_value=16, max_value=2048))
def test_property_roundtrip(data, codec, itemsize, block):
    assert C.decompress(C.compress(data, codec, itemsize, block)) == data


@settings(max_examples=30, deadline=None)
@given(n=st.integers(min_value=0, max_value=600),
       itemsize=st.sampled_from([2, 4, 8]))
def test_property_shuffle_inverse(n, itemsize):
    rng = np.random.default_rng(n)
    buf = rng.integers(0, 256, n * itemsize, dtype=np.uint8).tobytes()
    assert C.byte_unshuffle(C.byte_shuffle(buf, itemsize), itemsize) == buf


# ---------------------------------------------------------- corrupt payloads
# These MUST hold under `python -O` too (asserts are stripped there) — the
# decode path validates with real CorruptPayloadError raises, and the tier-1
# CI job re-runs this file with PYTHONOPTIMIZE=1.

def test_corrupt_bad_magic_raises():
    buf = bytearray(C.compress(b"hello world" * 10, "zlib"))
    buf[:4] = b"XXXX"
    with pytest.raises(C.CorruptPayloadError, match="magic"):
        C.decompress(bytes(buf))


def test_corrupt_truncated_header_raises():
    buf = C.compress(b"hello", "none")
    with pytest.raises(C.CorruptPayloadError, match="truncated"):
        C.decompress(buf[:C.HEADER.size - 2])


def test_corrupt_truncated_payload_raises():
    buf = C.compress(b"hello world" * 50, "zlib")
    with pytest.raises(C.CorruptPayloadError, match="truncated"):
        C.decompress(buf[:len(buf) - 3])


def test_corrupt_stream_raises_not_codec_error():
    """A flipped compressed byte must surface as CorruptPayloadError, not
    leak zlib.error / OSError from the underlying codec."""
    data = b"abcdefgh" * 200
    for codec in ("zlib", "bzip2"):
        buf = bytearray(C.compress(data, codec))
        for i in range(C.HEADER.size, len(buf)):
            buf[i] ^= 0xFF
        with pytest.raises(C.CorruptPayloadError):
            C.decompress(bytes(buf))


def test_corrupt_unknown_codec_id_raises():
    buf = bytearray(C.compress(b"hello", "none"))
    buf[4] = 0x7F                              # codec id byte
    with pytest.raises(C.CorruptPayloadError, match="codec"):
        C.decompress(bytes(buf))


def test_corrupt_payload_shape_mismatch_raises():
    arr = np.arange(64, dtype=np.float32)
    buf = C.array_payload(arr, "zlib")
    with pytest.raises(C.CorruptPayloadError):
        C.payload_to_array(buf, np.float32, (65,))


def test_corruption_detected_under_python_O():
    """Regression: the old `assert magic == MAGIC` vanished under -O and a
    rotted payload decoded into garbage. Run the decode path in a real
    `python -O` subprocess and require the exception to survive."""
    import os
    import pathlib
    import subprocess
    import sys
    code = (
        "import sys\n"
        # an `assert` would be stripped by the very flag under test
        "if not sys.flags.optimize:\n"
        "    raise SystemExit('optimize flag is off')\n"
        "from repro.core import compression as C\n"
        "buf = bytearray(C.compress(b'payload bytes' * 9, 'zlib'))\n"
        "buf[:4] = b'ROTN'\n"
        "try:\n"
        "    C.decompress(bytes(buf))\n"
        "except C.CorruptPayloadError:\n"
        "    print('CAUGHT')\n"
    )
    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src), PYTHONOPTIMIZE="1")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "CAUGHT"


# ------------------------------------------------------------- lossy codec

@pytest.mark.parametrize("dtype", [np.float16, np.float32, np.float64])
@pytest.mark.parametrize("spec,bound,rel", [
    ("lossy:1e-3", 1e-3, False),
    ("lossy:rel:1e-3", 1e-3, True),
])
def test_lossy_bound_holds_in_stored_dtype(dtype, spec, bound, rel):
    rng = np.random.default_rng(3)
    arr = (rng.normal(size=20_000) * 5).astype(dtype)
    buf = C.array_payload(arr, spec, block=32 * 1024)
    back = C.payload_to_array(buf, dtype, arr.shape)
    eps = bound * np.max(np.abs(arr.astype(np.float64))) if rel else bound
    err = np.max(np.abs(back.astype(np.float64) - arr.astype(np.float64)))
    assert err <= eps, (err, eps)
    if dtype is not np.float16:
        # f16: a bound under the ulp floor legitimately falls back to a
        # raw store (err == 0); wider floats must actually compress
        assert len(buf) < arr.nbytes


def test_lossy_beats_lossless_on_noise():
    """The point of the lossy codec: random floats barely compress
    losslessly but quantize-to-bound compresses well."""
    rng = np.random.default_rng(4)
    arr = rng.normal(size=100_000).astype(np.float32)
    lossless = C.array_payload(arr, "blosc")
    lossy = C.array_payload(arr, "lossy:1e-4")
    assert len(lossy) < 0.8 * len(lossless), (len(lossy), len(lossless))


def test_lossy_nonfinite_falls_back_lossless():
    arr = np.array([1.0, np.nan, np.inf, -np.inf, 2.5], dtype=np.float32)
    buf = C.array_payload(arr, "lossy:1e-3")
    back = C.payload_to_array(buf, np.float32, arr.shape)
    np.testing.assert_array_equal(back, arr)   # bitwise: fallback is lossless


def test_lossy_integer_dtype_falls_back_lossless():
    arr = np.arange(1000, dtype=np.int32)
    buf = C.array_payload(arr, "lossy:1e-3")
    np.testing.assert_array_equal(
        C.payload_to_array(buf, np.int32, arr.shape), arr)


def test_lossy_all_zero_rel_bound_falls_back():
    arr = np.zeros(1000, dtype=np.float32)
    buf = C.array_payload(arr, "lossy:rel:1e-3")
    np.testing.assert_array_equal(
        C.payload_to_array(buf, np.float32, arr.shape), arr)


@pytest.mark.parametrize("spec", ["lossy", "lossy:", "lossy:0", "lossy:-1",
                                  "lossy:nan", "lossy:rel:", "lossy:rel:0",
                                  "bogus"])
def test_bad_codec_specs_raise(spec):
    with pytest.raises(ValueError):
        C.parse_codec(spec)


def test_corrupt_lossy_subheader_raises():
    arr = np.random.default_rng(5).normal(size=5000).astype(np.float32)
    buf = C.array_payload(arr, "lossy:1e-3")
    hdr = C.HEADER.unpack_from(buf, 0)
    assert hdr[1] == C.CODEC_IDS["lossy"]
    # cut the block so even the lossy sub-header is gone
    cut = buf[:C.HEADER.size + C.LOSSY_SUB.size - 1]
    with pytest.raises(C.CorruptPayloadError):
        C.decompress(cut)


def test_corrupt_lossy_bad_qsize_raises():
    arr = np.random.default_rng(6).normal(size=5000).astype(np.float32)
    buf = bytearray(C.array_payload(arr, "lossy:1e-3"))
    # LOSSY_SUB = <dB: qsize is the 9th byte after the block header
    buf[C.HEADER.size + 8] = 3                 # not a valid int width
    with pytest.raises(C.CorruptPayloadError):
        C.decompress(bytes(buf))


# ----------------------------------------------------- pre-shuffled blocks

def test_preshuffled_payload_bit_identical_to_host():
    """The device contract: a pre-shuffled encode produces the SAME bytes
    as the host pipeline, so readers cannot tell the paths apart."""
    rng = np.random.default_rng(7)
    arr = np.cumsum(rng.normal(scale=1e-3, size=200_000)).astype(np.float32)
    host = C.array_payload(arr, "blosc", block=64 * 1024)
    shuffled = np.frombuffer(
        b"".join(C.byte_shuffle(arr.tobytes()[i:i + 64 * 1024], 4)
                 for i in range(0, arr.nbytes, 64 * 1024)),
        dtype=np.uint8).copy()
    chunk = C.PreshuffledChunk(shuffled, np.float32, arr.shape, 64 * 1024)
    assert C.array_payload_preshuffled(chunk, "blosc") == host


def test_preshuffled_raw_store_decodes():
    """Incompressible pre-shuffled bytes are raw-stored WITH the flag —
    decode must unshuffle them."""
    rng = np.random.default_rng(8)
    arr = rng.integers(0, 2**32, 4096, dtype=np.uint32)  # noise: raw store
    shuffled = np.frombuffer(C.byte_shuffle(arr.tobytes(), 4),
                             dtype=np.uint8).copy()
    chunk = C.PreshuffledChunk(shuffled, np.uint32, arr.shape, C.DEFAULT_BLOCK)
    buf = C.array_payload_preshuffled(chunk, "blosc")
    hdr = C.HEADER.unpack_from(buf, 0)
    assert hdr[1] == C.CODEC_IDS["none"] and hdr[3] & C.FLAG_PRESHUFFLED
    np.testing.assert_array_equal(
        C.payload_to_array(buf, np.uint32, arr.shape), arr)


def test_corrupt_truncated_preshuffled_block_raises():
    rng = np.random.default_rng(9)
    arr = rng.integers(0, 2**32, 4096, dtype=np.uint32)
    shuffled = np.frombuffer(C.byte_shuffle(arr.tobytes(), 4),
                             dtype=np.uint8).copy()
    chunk = C.PreshuffledChunk(shuffled, np.uint32, arr.shape, C.DEFAULT_BLOCK)
    buf = C.array_payload_preshuffled(chunk, "blosc")
    with pytest.raises(C.CorruptPayloadError):
        C.decompress(buf[:len(buf) - 7])


def test_preshuffled_rejects_non_device_codec():
    chunk = C.PreshuffledChunk(np.zeros(16, np.uint8), np.float32, (4,), 1024)
    with pytest.raises(ValueError):
        C.array_payload_preshuffled(chunk, "bzip2")


def test_old_format_flags_zero_reads_bit_identical():
    """Forward compat: payloads written before the flags field existed
    (flags == 0 everywhere) decode unchanged."""
    rng = np.random.default_rng(10)
    arr = rng.normal(size=50_000).astype(np.float64)
    buf = C.array_payload(arr, "blosc", block=64 * 1024)
    for off, _cid, _isz, flags, _raw, _comp in C.iter_block_headers(buf):
        assert flags == 0                      # host path writes no flags
    np.testing.assert_array_equal(
        C.payload_to_array(buf, np.float64, arr.shape), arr)


# ------------------------------------------------- decompress scaling path

def test_many_block_decompress_preallocates():
    """The O(n^2) fix: decompress pre-scans headers and writes into one
    preallocated buffer. Equality over many small blocks guards the path."""
    data = bytes(range(256)) * 2048            # 512 KiB
    buf = C.compress(data, "zlib", itemsize=1, block=1024)   # 512 blocks
    assert C.decompress(buf) == data


def test_payload_to_array_zero_copy_single_raw_block():
    arr = np.random.default_rng(11).integers(0, 255, 4096, dtype=np.uint8)
    buf = C.array_payload(arr, "none")
    back = C.payload_to_array(buf, np.uint8, arr.shape)
    np.testing.assert_array_equal(back, arr)
    assert back.base is not None               # a view, not a copy
    assert not back.flags.writeable            # of the (immutable) payload


# -------------------------------------------------------- device pipeline

def test_device_array_payload_matches_host():
    jnp = pytest.importorskip("jax.numpy")
    rng = np.random.default_rng(12)
    arr = np.cumsum(rng.normal(scale=1e-3, size=300_000)).astype(np.float32)
    host = C.array_payload(arr, "blosc", block=256 * 1024)
    dev, stats = C.device_array_payload(jnp.asarray(arr), "blosc",
                                        block=256 * 1024)
    assert dev == host
    assert stats.device_bytes == arr.nbytes


def test_device_precondition_roundtrip_and_stats():
    jnp = pytest.importorskip("jax.numpy")
    rng = np.random.default_rng(13)
    arr = rng.normal(size=(100, 700)).astype(np.float32)
    chunk = C.device_precondition(jnp.asarray(arr), block=64 * 1024)
    assert chunk.shape == arr.shape and chunk.dtype == np.float32
    assert chunk.vmin == float(np.min(arr))
    assert chunk.vmax == float(np.max(arr))
    buf = C.array_payload_preshuffled(chunk, "blosc")
    assert buf == C.array_payload(arr, "blosc", block=64 * 1024)


@pytest.mark.parametrize("shape,dtype,block", [
    ((50_000, 3), np.float32, 64 * 1024),     # narrow minor dim, row-split blocks
    ((4_001,), np.int32, 4_002),              # blocks that split items
    ((8, 64, 2), np.float32, 1024),
    ((0, 3), np.float32, 1024),               # empty
    ((3_000,), np.uint8, 1024),               # itemsize 1: never shuffled
])
def test_device_payload_matches_host_any_layout(shape, dtype, block):
    """Compressible data, so no block is stored raw (a raw store keeps
    the pre-shuffle flag, and then only the decoded arrays agree)."""
    jnp = pytest.importorskip("jax.numpy")
    size = int(np.prod(shape))
    arr = (np.arange(size) % 7).astype(dtype).reshape(shape)
    host = C.array_payload(arr, "blosc", block=block)
    dev, _ = C.device_array_payload(jnp.asarray(arr), "blosc", block=block)
    assert dev == host
    chunk = C.device_precondition(jnp.asarray(arr), block=block)
    assert C.array_payload_preshuffled(chunk, "blosc") == host
