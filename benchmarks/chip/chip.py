"""What every cell needs of the chip: refusal without one, the compile
cache, JAX's compile events, the device's memory peak and the run's key.
The helpers follow `chip_smoke.py` at the root, copied so that the
benchmark imports nothing of it."""
from __future__ import annotations

import collections
import os
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / ".jax_cache"
# series, checkpoints and traces of a run; removed at its end
WORK = pathlib.Path(__file__).resolve().parent / ".work"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chip(n_chips: int):
    """The first `n_chips` TPU devices; raises `NoChip` on any other
    backend or when fewer are there."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"JAX found no TPU (default backend {backend!r}); "
                     f"this benchmark measures the chip only")
    devices = jax.devices()
    if len(devices) < n_chips:
        raise NoChip(f"the cell needs {n_chips} TPU chips, JAX reports "
                     f"{len(devices)}")
    return devices[:n_chips]


def enable_compile_cache() -> pathlib.Path:
    """JAX's persistent compile cache: `JAX_COMPILATION_CACHE_DIR` when it
    is set, else `.jax_cache/` at the root of the checkout. Every program
    is cached, however quick its compile, so that a warm run compiles
    nothing."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = pathlib.Path(env) if env else CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """XLA compiles (cache loads included) per jitted function, from JAX's
    `/jax/core/compile/backend_compile_duration` events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count[kw.get("fun_name", "?")] += 1

    def total(self) -> int:
        return sum(self.count.values())

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def device_peak_bytes(devices) -> int:
    """`peak_bytes_in_use` of the fullest device (0 where not reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def seed_key(seed: int):
    """The key that a run's `--seed` names: any whole number below 2**63
    (the low 32 bits seed the key, the rest are folded in)."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
