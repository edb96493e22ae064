"""Monte-Carlo collisions — the paper's use case (§III-C): electron-impact
ionization e + D -> 2e + D+ in an unbounded unmagnetized plasma, where the
neutral density decays as  dn/dt = -n * n_e * R  (R: ionization rate
coefficient). Each MC event transfers weight from the neutral species to a
newly spawned electron/ion pair."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.pic.particles import Species, spawn


def ionize(key, electrons: Species, ions: Species, neutrals: Species,
           *, rate_R: float, dt: float, L: float, n_cells: int,
           electron_density_per_cell):
    """One MC ionization substep.

    For every alive NEUTRAL macro-particle, the ionization probability over
    dt is  p = 1 - exp(-n_e(x) * R * dt)  with n_e interpolated at the
    neutral's position. On an event the neutral dies and an electron/ion
    pair inherits its position and weight.
    """
    dx = L / n_cells
    ci = jnp.clip((neutrals.x / dx).astype(jnp.int32), 0, n_cells - 1)
    ne_local = electron_density_per_cell[ci]                     # [C]
    p = 1.0 - jnp.exp(-ne_local * rate_R * dt)
    u = jax.random.uniform(key, neutrals.x.shape)
    event = (u < p) & (neutrals.alive > 0)

    # neutral dies
    new_neutrals = dataclasses.replace(
        neutrals, alive=jnp.where(event, 0.0, neutrals.alive))

    # electron + ion inherit position/weight; thermal kick for the electron
    kv = jax.random.fold_in(key, 1)
    v_e = neutrals.v + jax.random.normal(kv, neutrals.v.shape) * 1e-2
    new_electrons, drop_e = spawn(electrons, neutrals.x, v_e, neutrals.w, event)
    new_ions, drop_i = spawn(ions, neutrals.x, neutrals.v, neutrals.w, event)
    n_events = jnp.sum(event)
    return (new_electrons, new_ions, new_neutrals,
            {"ionizations": n_events, "dropped": drop_e + drop_i})
