"""Uniform-bin weighted histograms on the device, without `searchsorted`.

`jnp.histogram` bins with `searchsorted(edges, a, side='right')`, a while
loop of binary-search steps that gathers from the edges for every element.
Here the bin of each element is the number of edges at or below it, counted
by one compare per edge, and each bin's sum is a compare-and-sum over the
bins; both fuse into elementwise and reduce loops. The particle axis is
walked in blocks, so what is live besides the inputs is one block's
intermediates, never an array of the inputs' length.

On a TPU v5e, one species' two 64-bin histograms at 2^23 particles take
about 5 ms this way, 1.1 s through `jnp.histogram`, and 154 ms with a
scatter-add into the bins in place of the compare-and-sum.

The edges are `jnp.histogram_bin_edges`, the bins and the treatment of the
last edge and of values out of range (dropped, as are NaN and +-inf) are
`jnp.histogram`'s: unit weights give the same counts bit for bit, other
weights the same sums up to float32 summation order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

BLOCK = 1 << 18          # elements per block of the particle axis


def bin_index(a, edges):
    """`searchsorted(edges, a, side='right')` with `a == edges[-1]` moved
    into the last bin, as `jnp.histogram` does: 1..len(edges)-1 are the
    bins, 0 and len(edges) lie outside. NaN reads 0 (`searchsorted` gives
    len(edges)): outside either way."""
    n = edges.shape[0]
    idx = jnp.zeros(a.shape, jnp.int32)
    for j in range(n):
        idx = idx + (a >= edges[j]).astype(jnp.int32)
    return jnp.where(a == edges[n - 1], n - 1, idx)


def bin_sums(idx, weights, bins: int):
    """Per-bin sums of `weights` over bins 1..`bins` of `idx`."""
    hit = idx[None, :] == jnp.arange(1, bins + 1, dtype=jnp.int32)[:, None]
    return jnp.sum(jnp.where(hit, weights[None, :], 0), axis=1)


def over_blocks(fn, n: int):
    """The sum of `fn(start, size)` over the blocks that cover [0, n)."""
    full, tail = divmod(n, BLOCK)
    out = None
    if full:
        out = lax.fori_loop(
            1, full,
            lambda i, acc: jax.tree.map(jnp.add, acc, fn(i * BLOCK, BLOCK)),
            fn(0, BLOCK))
    if tail:
        last = fn(full * BLOCK, tail)
        out = last if out is None else jax.tree.map(jnp.add, out, last)
    return out


def _block(x, start, size):
    return lax.dynamic_slice_in_dim(x, start, size, 0)


@functools.partial(jax.jit, static_argnames=("bins", "range"))
def uniform_histogram(a, weights, *, bins: int, range: tuple):
    """`jnp.histogram(a, bins, range, weights=weights)[0]` for 1-D `a`."""
    edges = jnp.histogram_bin_edges(a, bins, range)
    return over_blocks(
        lambda s, k: bin_sums(bin_index(_block(a, s, k), edges),
                              _block(weights, s, k), bins),
        a.shape[0])


@functools.partial(jax.jit,
                   static_argnames=("mass", "bins", "v_range", "e_range"))
def species_histograms(v, w, alive, *, mass: float, bins: int,
                       v_range: tuple, e_range: tuple):
    """One species' speed and kinetic-energy histograms, weighted by
    `w * alive`: `jnp.histogram` of `|v|` over `v_range` and of
    `mass |v|^2 / 2` over `e_range`, in one program."""
    dt = v.dtype
    v_edges = jnp.histogram_bin_edges(jnp.zeros((), dt), bins, v_range)
    e_edges = jnp.histogram_bin_edges(jnp.zeros((), dt), bins, e_range)

    def block(s, k):
        vmag = jnp.linalg.norm(_block(v, s, k), axis=-1)
        wts = _block(w, s, k) * _block(alive, s, k)
        energy = 0.5 * mass * vmag**2
        return (bin_sums(bin_index(vmag, v_edges), wts, bins),
                bin_sums(bin_index(energy, e_edges), wts, bins))

    return over_blocks(block, v.shape[0])
