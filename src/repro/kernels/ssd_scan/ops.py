"""Public wrapper: [b,s,h,p] layout like models/ssm, padding, dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.ssd_scan.kernel import DEFAULT_CHUNK, ssd_scan_tpu


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = DEFAULT_CHUNK,
             interpret: bool | None = None):
    """Same contract as models.ssm.ssd_chunked (y only). x:[b,s,h,p],
    dt:[b,s,h], A/D:[h], B/C:[b,s,n]."""
    interpret = auto_interpret() if interpret is None else interpret
    b, s, h, p = x.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    xm = x.transpose(0, 2, 1, 3)                      # [b,h,s,p]
    dtm = dt.transpose(0, 2, 1)                       # [b,h,s]
    y = ssd_scan_tpu(xm, dtm, A, B, C, D, chunk=chunk, interpret=interpret)
    y = y.transpose(0, 2, 1, 3)
    return y[:, :s] if pad else y
