"""Kernel micro-benchmarks: jnp production paths and Pallas kernels in
interpret mode, timed on the host CPU. These are correctness-path
latencies, not TPU numbers: device timings come only from a run on the
chip (`chip_smoke.py`)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit


def _bench(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run():
    key = jax.random.PRNGKey(0)

    # flash attention (jnp custom-vjp production path)
    from repro.models.attention import flash_attention_jnp
    B, S, H, D = 1, 1024, 4, 64
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, S, H, D),
                                 jnp.float32).astype(jnp.bfloat16)
               for i in range(3))
    f = jax.jit(lambda q, k, v: flash_attention_jnp(q, k, v, q_chunk=256,
                                                    kv_chunk=256))
    us = _bench(f, q, k, v)
    flops = 4 * B * S * S * H * D
    emit("kernel/flash_jnp S=1024", us, f"{flops / (us / 1e6) / 1e9:.1f}GFLOP/s")

    # ssd chunked (jnp production path)
    from repro.models.ssm import ssd_chunked
    b, s, h, p, n = 1, 1024, 8, 64, 64
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    Bm = jax.random.normal(ks[3], (b, s, n)) * 0.3
    Cm = jax.random.normal(ks[4], (b, s, n)) * 0.3
    Dv = jnp.ones((h,))
    g = jax.jit(lambda *a: ssd_chunked(*a, chunk=128)[0])
    us = _bench(g, x, dt, A, Bm, Cm, Dv)
    emit("kernel/ssd_jnp S=1024", us,
         f"{b * s * h * p * n * 6 / (us / 1e6) / 1e9:.1f}GFLOP/s")

    # deposit (jnp oracle vs pallas-interpret)
    from repro.kernels.deposit import ops as dops
    from repro.pic.grid import deposit_cic
    N, C = 1 << 16, 1024
    xs = jax.random.uniform(key, (N,), jnp.float32)
    w = jnp.ones((N,), jnp.float32)
    al = jnp.ones((N,), jnp.float32)
    us = _bench(jax.jit(lambda *a: deposit_cic(*a, C, 1.0 / C)), xs, w, al)
    emit("kernel/deposit_jnp N=65536", us, f"{N / us:.0f}particles/us")

    # bitshuffle host path (used by the blosc codec)
    from repro.core.compression import byte_shuffle
    buf = np.random.default_rng(0).bytes(8 << 20)
    t0 = time.perf_counter()
    byte_shuffle(buf, 4)
    us = (time.perf_counter() - t0) * 1e6
    emit("kernel/byte_shuffle 8MiB", us,
         f"{len(buf) / (us / 1e6) / 2**30:.2f}GiB/s")


if __name__ == "__main__":
    run()
