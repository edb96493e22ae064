"""BIT1-like 1D3V electrostatic PIC-MC simulation driver.

Implements the five-phase PIC cycle of the paper (§II): deposition ->
smoothing -> field solve -> MC collisions/walls -> push. The paper's use
case (§III-C — neutral ionization in an unbounded unmagnetized plasma,
no field solver or smoother) is `PicConfig(field_solve=False,
boundary='periodic')` with three species (e, D+, D).

Diagnostics mirror BIT1's five I/O knobs: `mvstep`-periodic profile/
distribution diagnostics (.dat analogue -> openPMD meshes) and
`dmpstep`-periodic full particle state dumps (.dmp analogue -> openPMD
particle species through the JBP engine).

With `open_diagnostic_series(..., async_io=True)` (the default) a dump only
snapshots host arrays and enqueues the step: compression, aggregation and
the subfile/metadata writes happen on the engine's background pipeline
while the next `pic_run_chunk` is already pushing/depositing on device —
the paper's "I/O as a background activity" claim, end to end.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dxt import TRACER
from repro.pic import collisions, fields, grid
from repro.pic.histogram import species_histograms
from repro.pic.particles import Species, init_species, push


@dataclasses.dataclass(frozen=True)
class PicConfig:
    n_cells: int = 1024
    L: float = 1.0
    dt: float = 1e-3
    capacity: int = 1 << 15           # per species
    n_electrons: int = 8192
    n_ions: int = 8192
    n_neutrals: int = 8192
    v_thermal_e: float = 1.0
    v_thermal_i: float = 0.02
    rate_R: float = 0.05              # ionization rate coefficient
    boundary: str = "periodic"        # periodic | absorbing
    field_solve: bool = False         # paper's use case skips solver+smoother
    smoothing: bool = False
    eps0: float = 1.0

    @property
    def dx(self):
        return self.L / self.n_cells


class PicState(NamedTuple):
    electrons: Species
    ions: Species
    neutrals: Species
    key: jnp.ndarray
    step: jnp.ndarray
    wall_flux_e: jnp.ndarray
    wall_flux_i: jnp.ndarray
    total_ionizations: jnp.ndarray


def init_sim(cfg: PicConfig, key) -> PicState:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e = init_species(k1, cfg.capacity, cfg.n_electrons, L=cfg.L,
                     v_thermal=cfg.v_thermal_e, charge=-1.0, mass=1.0)
    i = init_species(k2, cfg.capacity, cfg.n_ions, L=cfg.L,
                     v_thermal=cfg.v_thermal_i, charge=+1.0, mass=1836.0)
    n = init_species(k3, cfg.capacity, cfg.n_neutrals, L=cfg.L,
                     v_thermal=cfg.v_thermal_i, charge=0.0, mass=1836.0)
    z = jnp.zeros((), jnp.float32)
    return PicState(e, i, n, k4, jnp.zeros((), jnp.int32), z, z, z)


@functools.partial(jax.jit, static_argnums=(1,))
def pic_step(state: PicState, cfg: PicConfig) -> PicState:
    e, i, n = state.electrons, state.ions, state.neutrals
    dx = cfg.dx

    # 1-2. deposition + smoothing
    rho_e = grid.deposit_cic(e.x, e.w, e.alive, cfg.n_cells, dx)
    rho_i = grid.deposit_cic(i.x, i.w, i.alive, cfg.n_cells, dx)
    rho = i.charge * rho_i + e.charge * rho_e
    if cfg.smoothing:
        rho = grid.smooth_121(rho)

    # 3. field solve
    if cfg.field_solve:
        _, E = fields.solve_poisson(rho, dx, cfg.eps0)
    else:
        E = jnp.zeros((cfg.n_cells,), jnp.float32)

    # 4. MC collisions (ionization) — needs n_e per cell
    key, sub = jax.random.split(state.key)
    e, i, n, info = collisions.ionize(
        sub, e, i, n, rate_R=cfg.rate_R, dt=cfg.dt, L=cfg.L,
        n_cells=cfg.n_cells, electron_density_per_cell=rho_e * dx)

    # 5. push + walls
    e, wf_e = push(e, grid.gather_field(E, e.x, dx), cfg.dt, cfg.L,
                   boundary=cfg.boundary)
    i, wf_i = push(i, grid.gather_field(E, i.x, dx), cfg.dt, cfg.L,
                   boundary=cfg.boundary)
    n, _ = push(n, jnp.zeros_like(n.x), cfg.dt, cfg.L, boundary=cfg.boundary)

    return PicState(e, i, n, key, state.step + 1,
                    state.wall_flux_e + wf_e, state.wall_flux_i + wf_i,
                    state.total_ionizations + info["ionizations"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def pic_run_chunk(state: PicState, cfg: PicConfig, n_steps: int) -> PicState:
    return jax.lax.fori_loop(0, n_steps, lambda _, s: pic_step(s, cfg), state)


# ---------------------------------------------------------------- diagnostics
def diagnostics(state: PicState, cfg: PicConfig, *, v_bins: int = 64) -> dict:
    """BIT1 'slow' diagnostics: plasma profiles + velocity/energy dists."""
    out = {}
    for name, sp in (("e", state.electrons), ("D_plus", state.ions),
                     ("D", state.neutrals)):
        dens = grid.deposit_cic(sp.x, sp.w, sp.alive, cfg.n_cells, cfg.dx)
        out[f"density/{name}"] = np.asarray(dens)
        vdist, edist = species_histograms(
            sp.v, sp.w, sp.alive, mass=sp.mass, bins=v_bins,
            v_range=(0.0, 5.0), e_range=(0.0, 10.0))
        out[f"vdist/{name}"] = np.asarray(vdist)
        out[f"edist/{name}"] = np.asarray(edist)
        out[f"count/{name}"] = float(sp.count())
    out["wall_flux/e"] = float(state.wall_flux_e)
    out["wall_flux/i"] = float(state.wall_flux_i)
    out["ionizations"] = float(state.total_ionizations)
    return out


def write_diagnostics_openpmd(series, state: PicState, cfg: PicConfig,
                              *, n_io_ranks: int = 8, diag: Optional[dict] = None):
    """Stream one diagnostic snapshot through openPMD (datfile analogue).
    Pass a precomputed `diag` to share one snapshot between the openPMD
    write and in-situ consumers (reducers / SST streams)."""
    step = int(state.step)
    it = series.iterations[step]
    it.time = step * cfg.dt
    if diag is None:
        diag = diagnostics(state, cfg)
    for name, arr in diag.items():
        if not isinstance(arr, np.ndarray):
            continue
        rc = it.meshes[name.replace("/", "_")][""]
        rc.reset_dataset(arr.dtype, arr.shape)
        # profile diagnostics are rank-decomposed like BIT1's grid split
        n = arr.shape[0]
        per = max(n // n_io_ranks, 1)
        for r in range(min(n_io_ranks, n)):
            lo = r * per
            hi = n if r == min(n_io_ranks, n) - 1 else (r + 1) * per
            rc.store_chunk(arr[lo:hi], offset=(lo,), rank=r)
    return it


def open_diagnostic_series(path, *, n_io_ranks: int = 8, async_io: bool = True,
                           engine_config=None, queue_depth: int = 2,
                           parallel_io: int = 0,
                           device_compress: bool = False):
    """Series for BIT1-style diagnostic output, async by default so dumps
    never stall the push/deposit loop.

    `parallel_io=W` opts in to the multi-process write plane: W real
    writer processes stream into W aggregated subfiles (compression and
    subfile appends leave this process entirely, chunks shipped over
    shared-memory rings), each dump committed by a two-phase commit. The
    async default COMPOSES with it — the commit runs behind a bounded
    snapshot queue (`async_commit`), so the push/deposit loop sees
    neither compression nor commit latency.

    `device_compress=True` turns on the on-chip compression precondition
    for jax.Array chunks stored on the series. The writers in this module
    store host copies (`np.asarray`), so on the PIC path it has no effect
    yet."""
    from repro.core.bp_engine import EngineConfig
    from repro.core.openpmd import Series
    if engine_config is None:
        engine_config = EngineConfig(aggregators=min(4, n_io_ranks),
                                     codec="blosc")
    dc = True if device_compress else None   # None: engine_config decides
    if parallel_io:
        return Series(path, "w", n_ranks=n_io_ranks,
                      engine_config=engine_config, parallel_io=parallel_io,
                      async_commit=async_io, queue_depth=queue_depth,
                      device_compress=dc)
    return Series(path, "w", n_ranks=n_io_ranks, engine_config=engine_config,
                  async_io=async_io, queue_depth=queue_depth,
                  device_compress=dc)


def run_with_diagnostics(state: PicState, cfg: PicConfig, series=None, *,
                         n_chunks: int, steps_per_chunk: int,
                         dump_every: int = 0, n_io_ranks: int = 8,
                         reducers=None, stream=None) -> PicState:
    """BIT1 main loop: jitted compute chunks interleaved with mvstep
    diagnostics (every chunk) and dmpstep particle dumps (every
    `dump_every` chunks). With an async series, `flush()` returns after the
    snapshot and the next chunk's compute overlaps the write pipeline; the
    final `drain()` is the durability barrier before returning.

    In-situ hooks (repro.insitu): each chunk's diagnostic snapshot is
    computed ONCE and fanned out to
      * `series`   — openPMD persistence (optional: pass None to run a
                     pure in-situ pipeline with no filesystem in the loop),
      * `stream`   — an `SstStream`; consumers (e.g. `attach_reducers`)
                     analyze live while the next chunk computes,
      * `reducers` — a `ReducerSet` updated inline on the producer thread
                     (run-time diagnostics without a consumer thread).
    """
    for c in range(n_chunks):
        with TRACER.span("pic_chunk"):
            state = pic_run_chunk(state, cfg, steps_per_chunk)
            step = int(state.step)
        with TRACER.span("diagnostics"):
            diag = diagnostics(state, cfg)
        arrays = {k: v for k, v in diag.items() if isinstance(v, np.ndarray)}
        if series is not None:
            write_diagnostics_openpmd(series, state, cfg,
                                      n_io_ranks=n_io_ranks, diag=diag)
            if dump_every and (c + 1) % dump_every == 0:
                write_particle_dump_openpmd(series, state, cfg,
                                            n_io_ranks=n_io_ranks)
            series.flush()
        if stream is not None:
            stream.begin_step(step)
            for name, arr in arrays.items():
                stream.put(name, arr, global_shape=arr.shape,
                           offset=(0,) * arr.ndim)
            stream.end_step()
        if reducers is not None:
            reducers.update(step, arrays)
    if series is not None:
        series.drain()
    return state


def write_particle_dump_openpmd(series, state: PicState, cfg: PicConfig,
                                *, n_io_ranks: int = 8):
    """Full particle state (dmp analogue): species records chunked by rank.
    Every record is copied to the host first, in one span."""
    step = int(state.step)
    it = series.iterations[step]
    species_all = (("e", state.electrons), ("D_plus", state.ions),
                   ("D", state.neutrals))
    with TRACER.span("dump_copy", path=f"step.{step}") as span:
        records = [{"position/x": np.asarray(sp.x),
                    "momentum/x": np.asarray(sp.v[:, 0]),
                    "momentum/y": np.asarray(sp.v[:, 1]),
                    "momentum/z": np.asarray(sp.v[:, 2]),
                    "weighting": np.asarray(sp.w * sp.alive)}
                   for _, sp in species_all]
        span.length = sum(a.nbytes for r in records for a in r.values())
    for (name, sp), arrays in zip(species_all, records):
        species = it.particles[name]
        C = sp.capacity
        per = max(C // n_io_ranks, 1)
        for rec_name, arr in arrays.items():
            rec, comp = (rec_name.split("/") + [""])[:2]
            rc = species[rec][comp]
            rc.reset_dataset(arr.dtype, arr.shape)
            for r in range(min(n_io_ranks, C)):
                lo = r * per
                hi = C if r == min(n_io_ranks, C) - 1 else (r + 1) * per
                rc.store_chunk(arr[lo:hi], offset=(lo,), rank=r)
    return it
