"""Pallas kernels. Each one runs compiled on a TPU and in interpret mode
on the CPU backend, where the tests run; no other backend is supported."""
from __future__ import annotations

import jax


def auto_interpret() -> bool:
    """Interpret mode for the backend JAX is on: False on a TPU, True on
    the CPU. Any other backend raises, so a kernel never falls back to
    the interpreter in silence."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels here run on 'tpu' (compiled) or "
                       f"'cpu' (interpreted), not on {backend!r}")
