"""Public wrapper: pad particles/cells to kernel tiles, dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.deposit.kernel import TILE_C, TILE_P, deposit_tpu


def deposit(x, w, alive, *, n_cells: int, dx: float,
            interpret: bool | None = None) -> jax.Array:
    interpret = auto_interpret() if interpret is None else interpret
    n = x.shape[0]
    pad_p = (-n) % TILE_P
    # park padded particles far outside the grid: clipped to the last cell
    # with zero weight, they contribute nothing.
    xp = jnp.pad(x, (0, pad_p))
    wp = jnp.pad(w * alive, (0, pad_p))
    pad_c = (-n_cells) % TILE_C
    rho = deposit_tpu(xp, wp, n_cells=n_cells + pad_c,
                      clip_max=n_cells - 1, dx=dx, interpret=interpret)
    return rho[:n_cells]
