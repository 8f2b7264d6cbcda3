"""Pallas TPU flash attention (causal / sliding-window / GQA).

Blockwise online-softmax attention with explicit VMEM tiling:

  grid = (batch, q_heads, num_q_blocks, num_kv_blocks)   [kv innermost]

TPU grid steps execute sequentially, so the running (m, l, acc) state for
one q tile is carried across kv grid steps in VMEM scratch and flushed to
the output block on the last kv step.  GQA is handled in the BlockSpec
index maps (kv head = q head // group) — no materialized head broadcast.

Layout: (B, S, H, D) is viewed as (B, S, H*D) (a free reshape), and a
block is one head's (seq tile, head_dim) slab at lane offset head * D.  So
the last two block dims, the ones Mosaic tiles, are (128, head_dim): a
head_dim that is a multiple of 128 compiles for the TPU.

MXU alignment: q/kv tiles default to 128 x head_dim with fp32 accumulation.
Fully-masked (q, kv) tiles are skipped with ``pl.when`` (the causal upper
triangle costs no FLOPs beyond the guard).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale: float, bq: int, bk: int, causal: bool, window: int,
                 seq_q: int, seq_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions (q right-aligned against k for decode-style calls)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (seq_k - seq_q)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)

    first_q = iq * bq + (seq_k - seq_q)
    last_q = first_q + bq - 1
    first_k = ik * bk
    last_k = first_k + bk - 1
    run = jnp.bool_(True)
    if causal:
        run &= first_k <= last_q          # tile not fully above the diagonal
    if window > 0:
        run &= last_k > first_q - window  # tile not fully outside the window

    @pl.when(run)
    def _compute():
        q = q_ref[...].astype(jnp.float32)   # (bq, d)
        k = k_ref[...].astype(jnp.float32)   # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            mask = k_pos <= q_pos
            if window > 0:
                mask &= k_pos > q_pos - window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """q: (B, S, H, D); k, v: (B, T, Kv, D) with H % Kv == 0."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(block_q, s)
    bk = min(block_k, t)
    if s % bq or t % bk:
        raise ValueError(f"seq lens ({s},{t}) must divide blocks ({bq},{bk})")
    grid = (b, h, s // bq, t // bk)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, scale=scale, bq=bq, bk=bk, causal=causal,
        window=window, seq_q=s, seq_k=t)

    q_spec = pl.BlockSpec((None, bq, d), lambda ib, ih, iq, ik: (ib, iq, ih))
    kv_spec = pl.BlockSpec((None, bk, d),
                           lambda ib, ih, iq, ik: (ib, ik, ih // g))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )(q.reshape(b, s, h * d), k.reshape(b, t, kv * d), v.reshape(b, t, kv * d))
    return out.reshape(b, s, h, d)
