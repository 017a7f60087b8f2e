"""Dispatch wrapper: GQA-aware flash attention entry point.

Maps (B, S, KH, G, dh) grouped-query layouts onto the (B*H, S, dh) kernel
by expanding KV heads at the wrapper level (the kernel itself streams KV
blocks, so the expansion is an indexing view, not extra HBM traffic on TPU).
Self-attention only (sq == skv) for the causal path.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import resolve_backend
from repro.kernels.attention.flash import flash_attention
from repro.kernels.attention.ref import attention_ref


def gqa_flash(q, k, v, *, causal=True, window=0, backend="auto",
              bq=128, bk=128):
    """q: (B, S, H, dh); k, v: (B, S, KH, dh) with H = KH * G."""
    b, s, h, dh = q.shape
    kh = k.shape[2]
    g = h // kh
    backend = resolve_backend(backend, q.dtype)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1).reshape(b * h, s, dh)
    if backend == "jnp":
        of = attention_ref(qf, kf, vf, causal=causal, window=window)
    else:
        of = flash_attention(qf, kf, vf, causal=causal, window=window,
                             bq=bq, bk=bk,
                             interpret=(backend == "interpret"))
    return of.reshape(b, h, s, dh).transpose(0, 2, 1, 3)
