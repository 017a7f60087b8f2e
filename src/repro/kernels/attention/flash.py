"""Flash attention (forward) Pallas TPU kernel — streaming-softmax tiling.

The §Perf residual for the attention-heavy cells (command-r, gemma3) is the
XLA path's materialized (S x S) fp32 logits plus full causal-masked matmuls.
This kernel streams KV blocks through VMEM with the online-softmax
recurrence and *skips* fully-masked blocks via ``pl.when`` — causal work is
a true S^2/2 and sliding-window work O(S·W) on TPU (grid points with no
live entries never touch the MXU).

Grid: (B*H, q_blocks, kv_blocks), kv innermost. Scratch: fp32 accumulator
(Bq, dh) + running max/sum (Bq,). Block sizes default to MXU/VPU-aligned
(128, 128); tests sweep small shapes in interpret mode against the jnp
oracle, including GQA head fan-out at the ops.py level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import not_on_chip


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                  *, sm_scale, causal, window, bq, bk, kv_len):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    q_pos = qi * bq + jax.lax.iota(jnp.int32, bq)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level liveness: skip kv blocks entirely above the causal
    # diagonal / outside the window
    first_q, last_q = qi * bq, qi * bq + bq - 1
    first_k, last_k = kj * bk, kj * bk + bk - 1
    live = jnp.asarray(True)
    if causal:
        live &= first_k <= last_q
    if window > 0:
        live &= last_k > first_q - window

    @pl.when(live)
    def _block():
        k_pos = kj * bk + jax.lax.iota(jnp.int32, bk)
        s = jnp.dot(q_ref[0], k_ref[0].T,
                    preferred_element_type=jnp.float32) * jnp.float32(sm_scale)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        mask &= (k_pos[None, :] < kv_len)
        s = jnp.where(mask, s, jnp.float32(-1e30))

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = corr * l_ref[...] + p.sum(axis=1)
        acc_ref[...] = (corr[:, None] * acc_ref[...]
                        + jnp.dot(p.astype(v_ref.dtype), v_ref[0],
                                  preferred_element_type=jnp.float32))
        m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...],
                                jnp.float32(1e-30))[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, dh); k, v: (BH, Skv, dh). Returns (BH, Sq, dh)."""
    if not interpret:
        not_on_chip("flash attention", "its index maps and rank-1 row "
                    "statistics were never made Mosaic-legal")
    bh, sq, dh = q.shape
    skv = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, skv)
    sq_pad = ((sq + bq - 1) // bq) * bq
    skv_pad = ((skv + bk - 1) // bk) * bk
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0)))
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_pad - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_pad - skv), (0, 0)))

    grid = (bh, sq_pad // bq, skv_pad // bk)
    kernel = functools.partial(
        _flash_kernel, sm_scale=1.0 / np.sqrt(dh), causal=causal,
        window=window, bq=bq, bk=bk, kv_len=skv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_pad, dh), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, dh), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32)],
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
