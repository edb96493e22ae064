"""Plain reference of the BIT1 neutral-ionization step (arXiv 2408.02869
§III-C), written from the paper's description and the configuration file,
in numpy float64. Imports nothing of the program under test.

One step, as the configuration states it (periodic, no field solve, so
E = 0 and the push only moves x by vx dt):

1. deposit the electron density by cloud-in-cell;
2. for every alive neutral, p = 1 - exp(-n_e(cell) R dt); an event when
   the neutral's uniform draw u < p: the neutral dies, and an electron
   (velocity of the neutral plus a 1e-2 normal kick) and an ion (velocity
   of the neutral) are born at its position with its weight, the k-th
   event in the k-th dead slot of each species, in slot order;
3. move every slot, x <- (x + vx dt) mod L.

The random draws are the configuration's: `jax.random` from the seed's
key, split as the state's key chain says. They are data, made on the
default device and fetched.

`dtype=bfloat16` gives the control: the same step with the state held in
bfloat16 after every operation, the nearest precision below the float32
that the configuration states.
"""
from __future__ import annotations

import numpy as np

SPECIES = ("electrons", "ions", "neutrals")
# openPMD species names of the dump, in the order of SPECIES
DUMP_NAMES = {"electrons": "e", "ions": "D_plus", "neutrals": "D"}
KICK = 1e-2          # thermal kick of a born electron
# |u - p| within MARGIN_ABS + MARGIN_REL * p: float32 noise may decide the
# event. The program's density carries float32's ~1e-3 of a cell in each
# particle's cloud-in-cell split, and p = 1 - exp(-x) keeps only exp's
# absolute precision near 1; on a v5e, events that the program and this
# reference decide apart, away from cell edges, lie up to ~1.6e-6 from p.
MARGIN_ABS = 1e-5
MARGIN_REL = 1e-5
CELL_EDGE = 1e-2     # x/dx this close to an integer: either cell may be read


class Draws:
    """The configuration's random numbers, computed by `jax.random` on the
    default device, one jitted call per kind of draw."""

    def __init__(self):
        import functools
        import jax
        import jax.numpy as jnp
        self.jax = jax

        @functools.partial(jax.jit, static_argnums=(1, 2, 3))
        def species(key, capacity, L, vt):
            kx, kv = jax.random.split(key)
            x = jax.random.uniform(kx, (capacity,), jnp.float32, 0.0, L)
            v = jax.random.normal(kv, (capacity, 3), jnp.float32) * vt
            return x, v

        @functools.partial(jax.jit, static_argnums=(1,))
        def step(key, capacity):
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, (capacity,))
            kick = jax.random.normal(jax.random.fold_in(sub, 1), (capacity, 3))
            return key, u, kick * KICK

        self.species = species
        self.step = step


def init(cfg: dict, key, draws: Draws, dtype=np.float64) -> dict:
    """State at step 0 from the seed's key: per species x, v, w, alive."""
    jax = draws.jax
    keys = jax.random.split(key, 4)
    state = {"key": keys[3], "step": 0}
    for name, k, n, vt in (
            ("electrons", keys[0], cfg["n_electrons"], cfg["v_thermal_e"]),
            ("ions", keys[1], cfg["n_ions"], cfg["v_thermal_i"]),
            ("neutrals", keys[2], cfg["n_neutrals"], cfg["v_thermal_i"])):
        x, v = jax.device_get(draws.species(k, cfg["capacity"], cfg["L"], vt))
        C = cfg["capacity"]
        state[name] = {"x": x.astype(dtype), "v": v.astype(dtype),
                       "w": np.ones(C, dtype),
                       "alive": (np.arange(C) < n).astype(dtype)}
    return state


def deposit(x, weight, n_cells: int, dx: float) -> np.ndarray:
    """Cloud-in-cell density on `n_cells` cells (edges clamped), float64."""
    x = np.asarray(x, np.float64)
    xi = x / dx
    i0 = np.floor(xi)
    frac = xi - i0
    i0 = i0.astype(np.int64)
    w = np.asarray(weight, np.float64)
    lo = np.clip(i0, 0, n_cells - 1)
    hi = np.clip(i0 + 1, 0, n_cells - 1)
    rho = np.bincount(lo, w * (1.0 - frac), minlength=n_cells)
    rho += np.bincount(hi, w * frac, minlength=n_cells)
    return rho / dx


def _round(a, dtype):
    return np.asarray(a, np.float64).astype(dtype)


def events(cfg: dict, state: dict, u: np.ndarray) -> dict:
    """Event masks over the neutrals' slots: `own`, the reference's
    decision u < p; `ambiguous`, where float32 rounding of the density,
    of p or of the neutral's cell could put u on either side of p;
    `certain`, the events that are not ambiguous. `gap` is |u - p|."""
    n_cells, L, dt, R = cfg["n_cells"], cfg["L"], cfg["dt"], cfg["rate_R"]
    dx = L / n_cells
    e, n = state["electrons"], state["neutrals"]
    ne = deposit(e["x"], np.asarray(e["w"], np.float64) * e["alive"],
                 n_cells, dx) * dx
    xi = np.asarray(n["x"], np.float64) / dx
    cells = np.clip(xi.astype(np.int64), 0, n_cells - 1)
    near = np.abs(xi - np.rint(xi)) < CELL_EDGE
    other = np.clip(np.where(xi - np.floor(xi) < 0.5, cells - 1, cells + 1),
                    0, n_cells - 1)
    p = 1.0 - np.exp(-ne[cells] * R * dt)
    p_other = np.where(near, 1.0 - np.exp(-ne[other] * R * dt), p)
    hi = np.maximum(p, p_other)
    margin = MARGIN_REL * hi + MARGIN_ABS
    u = np.asarray(u, np.float64)
    alive = n["alive"] > 0
    certain = alive & (u < np.minimum(p, p_other) - margin)
    ambiguous = alive & ~certain & (u < hi + margin)
    return {"certain": certain, "ambiguous": ambiguous,
            "own": alive & (u < p), "gap": np.abs(u - p)}


def advance(cfg: dict, state: dict, draws: Draws, *, dtype=np.float64,
            decided=None) -> tuple[dict, dict]:
    """One step. `decided(events)` returns the event mask to use, from
    the masks of `events`; by default an ambiguous event follows the
    reference's own p. Returns (next state, the masks and "events")."""
    jax = draws.jax
    C, L, dt = cfg["capacity"], cfg["L"], cfg["dt"]
    key, u, kick = jax.device_get(draws.step(state["key"], C))
    info = events(cfg, state, u)
    ev = (info["certain"] | (info["ambiguous"] & info["own"])
          if decided is None else decided(info))
    n = state["neutrals"]
    out = {"key": key, "step": state["step"] + 1}
    idx = np.flatnonzero(ev)
    born_v = {"electrons": _round(np.asarray(n["v"], np.float64)[idx]
                                  + kick[idx], dtype),
              "ions": n["v"][idx]}
    for name in SPECIES:
        sp = {k: np.array(a, copy=True) for k, a in state[name].items()}
        if name == "neutrals":
            sp["alive"][idx] = 0
        else:
            dead = np.flatnonzero(sp["alive"] <= 0)
            k = min(len(dead), len(idx))       # the rest would be dropped
            slots = dead[:k]
            sp["x"][slots] = n["x"][idx[:k]]
            sp["v"][slots] = born_v[name][:k]
            sp["w"][slots] = n["w"][idx[:k]]
            sp["alive"][slots] = 1
        x = np.asarray(sp["x"], np.float64) + np.asarray(sp["v"][:, 0],
                                                         np.float64) * dt
        sp["x"] = _round(np.mod(x, L), dtype)
        out[name] = sp
    return out, info | {"events": ev}


def diagnostics(cfg: dict, state: dict, v_bins: int = 64) -> dict:
    """Mesh records of one diagnostics call, as the configuration names
    them: density, |v| and energy distributions per species."""
    n_cells, L = cfg["n_cells"], cfg["L"]
    dx = L / n_cells
    out = {}
    for name in SPECIES:
        sp = state[name]
        dn = DUMP_NAMES[name]
        w = np.asarray(sp["w"], np.float64) * np.asarray(sp["alive"], np.float64)
        out[f"density_{dn}"] = deposit(sp["x"], w, n_cells, dx)
        vmag = np.sqrt(np.sum(np.asarray(sp["v"], np.float64) ** 2, axis=1))
        out[f"vdist_{dn}"] = np.histogram(vmag, bins=v_bins, range=(0.0, 5.0),
                                          weights=w)[0]
        mass = cfg["mass"][name]
        out[f"edist_{dn}"] = np.histogram(0.5 * mass * vmag ** 2, bins=v_bins,
                                          range=(0.0, 10.0), weights=w)[0]
    return out


def dump_record_names() -> list:
    return [f"{DUMP_NAMES[n]}/{r}" for n in SPECIES
            for r in ("position/x", "momentum/x", "momentum/y", "momentum/z",
                      "weighting")]


def mesh_names() -> list:
    return [f"{kind}_{DUMP_NAMES[n]}" for n in SPECIES
            for kind in ("density", "vdist", "edist")]


def dump_records(state: dict, dtype=np.float64) -> dict:
    """The particle records of a dump, by openPMD path, from a state."""
    out = {}
    for name in SPECIES:
        sp, dn = state[name], DUMP_NAMES[name]
        out[f"{dn}/position/x"] = np.asarray(sp["x"], dtype)
        for i, c in enumerate("xyz"):
            out[f"{dn}/momentum/{c}"] = np.asarray(sp["v"][:, i], dtype)
        out[f"{dn}/weighting"] = (np.asarray(sp["w"], dtype)
                                  * np.asarray(sp["alive"], dtype))
    return out


def l1_gap(got, want) -> float:
    """Sum of absolute differences over the sum of the reference's
    magnitudes: one record's share that differs."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).sum() / max(np.abs(want).sum(), 1e-300))


def state_gaps(cfg: dict, state: dict, dump: dict) -> dict:
    """Worst gaps of a dump's particle records from a reference state:
    position in cells (periodic), velocity over the species' thermal
    speed, and the count of slots whose weighting differs."""
    L = cfg["L"]
    dx = L / cfg["n_cells"]
    vt = {"electrons": cfg["v_thermal_e"], "ions": cfg["v_thermal_i"],
          "neutrals": cfg["v_thermal_i"]}
    want = dump_records(state)
    pos = vel = 0.0
    off = 0
    for name in SPECIES:
        dn = DUMP_NAMES[name]
        d = np.abs(np.asarray(dump[f"{dn}/position/x"], np.float64)
                   - want[f"{dn}/position/x"])
        pos = max(pos, float(np.minimum(d, L - d).max()) / dx)
        for c in "xyz":
            r = f"{dn}/momentum/{c}"
            vel = max(vel, float(np.abs(np.asarray(dump[r], np.float64)
                                        - want[r]).max()) / vt[name])
        r = f"{dn}/weighting"
        off += int(np.count_nonzero(np.asarray(dump[r], np.float64) != want[r]))
    return {"pos_gap_cells": pos, "vel_gap": vel, "alive_off": off}


class Control:
    """The control put in the program's place: the reference with its
    state held in bfloat16, giving the records the program would have
    written at each step of the first call."""

    def __init__(self, cfg: dict, key, draws: Draws):
        import ml_dtypes
        self.cfg, self.draws = cfg, draws
        self.dtype = ml_dtypes.bfloat16
        self.state = init(cfg, key, draws, dtype=self.dtype)

    def _at(self, step: int) -> dict:
        while self.state["step"] < step:
            self.state, _ = advance(self.cfg, self.state, self.draws,
                                    dtype=self.dtype)
        return self.state

    def dump(self, step: int) -> dict:
        return {k: np.asarray(v, np.float32)
                for k, v in dump_records(self._at(step), self.dtype).items()}

    def meshes(self, step: int) -> dict:
        return {k: np.asarray(v, self.dtype).astype(np.float32)
                for k, v in diagnostics(self.cfg, self._at(step)).items()}
