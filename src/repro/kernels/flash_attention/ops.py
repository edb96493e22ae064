"""Public wrapper: [B,S,H,D] layout in/out, seq padding, kernel dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret
from repro.kernels.flash_attention.kernel import flash_attention_tpu


def flash_attention(q, k, v, *, causal: bool = True, qc: int = 512,
                    kc: int = 512, interpret: bool | None = None):
    """q/k/v: [B,S,H,D] (H(q) == H(kv); GQA callers expand first)."""
    interpret = auto_interpret() if interpret is None else interpret
    B, S, H, D = q.shape
    qc = min(qc, S)
    kc = min(kc, S)
    pad = (-S) % max(qc, kc)
    if pad:
        # pad kv with zeros; padded q rows produce garbage rows we slice off,
        # padded kv columns are masked by causality (they sit at the end).
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, padw) for t in (q, k, v))
    qm = q.transpose(0, 2, 1, 3)
    km = k.transpose(0, 2, 1, 3)
    vm = v.transpose(0, 2, 1, 3)
    o = flash_attention_tpu(qm, km, vm, causal=causal, qc=qc, kc=kc,
                            kv_len=S, interpret=interpret)
    o = o.transpose(0, 2, 1, 3)
    return o[:, :S] if pad else o
