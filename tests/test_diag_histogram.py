"""The diagnostics' uniform-bin histograms against `jnp.histogram`.

`uniform_histogram` and `species_histograms` bin without `searchsorted`;
they must give `jnp.histogram`'s bins exactly: counts bit for bit, weighted
sums up to float32 summation order.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.pic.grid import deposit_cic
from repro.pic.histogram import BLOCK, bin_index, uniform_histogram
from repro.pic.simulation import (PicConfig, diagnostics, init_sim,
                                  pic_run_chunk)

BINS = 64
F32 = np.float32


def _edges(rng_):
    return np.asarray(jnp.histogram_bin_edges(jnp.zeros(1, jnp.float32),
                                              BINS, rng_))


def _values(case, rng_, gen):
    lo, hi = rng_
    edges = _edges(rng_)
    if case in ("random", "zero_weights"):
        # a fifth of the values below lo or above hi; 4000 is not a
        # multiple of 8 x 128
        return gen.uniform(lo - 0.1 * hi, hi + 0.1 * hi, 4000).astype(F32)
    if case == "edges":
        return np.concatenate([edges, np.nextafter(edges, F32(-np.inf)),
                               np.nextafter(edges, F32(np.inf))])
    if case == "hi":
        return np.array([hi] * 7 + [lo, np.nextafter(F32(hi), F32(0))], F32)
    if case == "nonfinite":
        finite = gen.uniform(lo, hi, 61).astype(F32)
        return np.concatenate([finite, np.array([np.nan, np.inf, -np.inf,
                                                 np.nan], F32)])
    if case == "long":
        # three whole blocks and a tail that is not a multiple of 8 x 128
        return gen.uniform(lo - 0.1 * hi, hi + 0.1 * hi,
                           3 * BLOCK + 1001).astype(F32)
    raise AssertionError(case)


def _weights(case, n, gen):
    if case == "zero_weights":
        return np.zeros(n, F32)
    if case == "long":
        # multiples of 2^-8 below 2: every bin's sum is exact in float32 in
        # any order, so this size checks the binning, not the rounding
        return (gen.integers(0, 512, n) / 256).astype(F32)
    return gen.uniform(0.0, 2.0, n).astype(F32)


@pytest.mark.parametrize("case", ["random", "edges", "hi", "nonfinite",
                                  "zero_weights", "long"])
@pytest.mark.parametrize("rng_", [(0.0, 5.0), (0.0, 10.0)])
def test_uniform_histogram_matches_jnp_histogram(rng_, case):
    gen = np.random.default_rng(zlib.crc32(f"{rng_} {case}".encode()))
    a = _values(case, rng_, gen)
    w = _weights(case, a.size, gen)

    edges = _edges(rng_)
    finite = ~np.isnan(a)
    want_idx = np.asarray(jnp.searchsorted(jnp.asarray(edges),
                                           jnp.asarray(a), side="right"))
    want_idx = np.where(a == edges[-1], BINS, want_idx)
    idx = np.asarray(jax.jit(bin_index)(jnp.asarray(a), jnp.asarray(edges)))
    np.testing.assert_array_equal(idx[finite], want_idx[finite])
    assert np.all(idx[~finite] == 0)          # NaN is out of range

    ones = np.ones_like(a)
    want, _ = jnp.histogram(a, BINS, rng_, weights=ones)
    got = uniform_histogram(jnp.asarray(a), jnp.asarray(ones), bins=BINS,
                            range=rng_)
    assert got.dtype == jnp.float32 and got.shape == (BINS,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    want, _ = jnp.histogram(a, BINS, rng_, weights=w)
    got = uniform_histogram(jnp.asarray(a), jnp.asarray(w), bins=BINS,
                            range=rng_)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    if case == "zero_weights":
        assert not np.any(np.asarray(got))


def test_diagnostics_histograms_match_jnp_histogram():
    cfg = PicConfig(n_cells=64, capacity=3000, n_electrons=2000,
                    n_ions=1000, n_neutrals=2500, rate_R=0.5, dt=1e-2)
    state = pic_run_chunk(init_sim(cfg, jax.random.PRNGKey(3)), cfg, 5)
    d = diagnostics(state, cfg)
    for name, sp in (("e", state.electrons), ("D_plus", state.ions),
                     ("D", state.neutrals)):
        vmag = jnp.linalg.norm(sp.v, axis=-1)
        wts = sp.w * sp.alive
        vdist, _ = jnp.histogram(vmag, bins=64, range=(0.0, 5.0),
                                 weights=wts)
        edist, _ = jnp.histogram(0.5 * sp.mass * vmag**2, bins=64,
                                 range=(0.0, 10.0), weights=wts)
        np.testing.assert_allclose(d[f"vdist/{name}"], np.asarray(vdist),
                                   rtol=1e-6)
        np.testing.assert_allclose(d[f"edist/{name}"], np.asarray(edist),
                                   rtol=1e-6)
        assert d[f"vdist/{name}"].dtype == np.float32
        np.testing.assert_array_equal(
            d[f"density/{name}"],
            np.asarray(deposit_cic(sp.x, sp.w, sp.alive, cfg.n_cells,
                                   cfg.dx)))
        assert d[f"count/{name}"] == float(sp.count())
    # every neutral's speed lies in range, and dead slots weigh nothing
    assert d["vdist/D"].sum() == d["count/D"] < cfg.capacity
