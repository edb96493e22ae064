"""Species containers (SoA, fixed capacity + alive mask) and the particle
mover — BIT1 is 1D3V: one spatial dim, three velocity dims."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Species:
    x: jnp.ndarray          # [C] position
    v: jnp.ndarray          # [C, 3] velocity (vx drives motion)
    w: jnp.ndarray          # [C] macro-particle weight
    alive: jnp.ndarray      # [C] float mask (1.0 alive / 0.0 dead)
    # static: part of the traced signature, never device arrays, so a state
    # that comes back from jit or from a checkpoint compiles to the same step
    charge: float = dataclasses.field(metadata=dict(static=True))
    mass: float = dataclasses.field(metadata=dict(static=True))

    @property
    def capacity(self):
        return self.x.shape[0]

    def count(self):
        return jnp.sum(self.alive)

    def density_weight(self):
        return jnp.sum(self.w * self.alive)


def init_species(key, capacity: int, n_active: int, *, L: float,
                 v_thermal: float, charge: float, mass: float,
                 weight: float = 1.0) -> Species:
    kx, kv = jax.random.split(key)
    x = jax.random.uniform(kx, (capacity,), jnp.float32, 0.0, L)
    v = jax.random.normal(kv, (capacity, 3), jnp.float32) * v_thermal
    alive = (jnp.arange(capacity) < n_active).astype(jnp.float32)
    w = jnp.full((capacity,), weight, jnp.float32)
    return Species(x, v, w, alive, charge, mass)


def push(sp: Species, E_at_p, dt: float, L: float, *,
         boundary: str = "periodic"):
    """Leapfrog: v += (q/m) E dt; x += vx dt. Returns (species, wall_flux)."""
    accel = (sp.charge / sp.mass) * E_at_p * dt
    v = sp.v.at[:, 0].add(accel)
    x = sp.x + v[:, 0] * dt
    wall = jnp.zeros((), jnp.float32)
    if boundary == "periodic":
        x = jnp.mod(x, L)
        alive = sp.alive
    else:  # absorbing walls (divertor plates) — BIT1 plasma-wall transition
        hit = ((x < 0.0) | (x >= L)) & (sp.alive > 0)
        wall = jnp.sum(jnp.where(hit, sp.w, 0.0))
        alive = jnp.where(hit, 0.0, sp.alive)
        x = jnp.clip(x, 0.0, L * (1.0 - 1e-7))
    return dataclasses.replace(sp, x=x, v=v, alive=alive), wall


def spawn(sp: Species, new_x, new_v, new_w, n_new_mask) -> Species:
    """Write new particles into dead slots (static shapes: the k-th new
    particle goes to the k-th dead slot; overflow is dropped & counted).

    new_x/new_v/new_w: candidate arrays [M]; n_new_mask: [M] bool."""
    C = sp.capacity
    dead_order = jnp.argsort(sp.alive, stable=True)      # dead slots first
    k = jnp.cumsum(n_new_mask.astype(jnp.int32)) - 1     # rank among events
    n_dead = jnp.sum(sp.alive <= 0).astype(jnp.int32)
    ok = n_new_mask & (k < n_dead)
    slot = dead_order[jnp.clip(k, 0, C - 1)]
    slot = jnp.where(ok, slot, C)                        # C = trash slot
    x = jnp.concatenate([sp.x, jnp.zeros((1,), sp.x.dtype)])
    v = jnp.concatenate([sp.v, jnp.zeros((1, 3), sp.v.dtype)])
    w = jnp.concatenate([sp.w, jnp.zeros((1,), sp.w.dtype)])
    al = jnp.concatenate([sp.alive, jnp.zeros((1,), sp.alive.dtype)])
    x = x.at[slot].set(new_x)
    v = v.at[slot].set(new_v)
    w = w.at[slot].set(new_w)
    al = al.at[slot].set(1.0)
    dropped = jnp.sum(n_new_mask & ~ok)
    return dataclasses.replace(sp, x=x[:C], v=v[:C], w=w[:C],
                               alive=al[:C]), dropped
