"""Pallas TPU flash attention, forward and backward (causal / sliding-window /
GQA).

Three kernels, each one pass over the (q tile, kv tile) pairs.  A grid step
takes one kv head's (bk, D) K and V tiles and the (bq, G*D) tile of the G
query heads that share them, so K and V are read once for the whole group:

  forward  grid (B, Kv, S/bq, T/bk), kv innermost.  Online softmax; writes
           the output and each row's logsumexp (f32), the backward's residual.
  dQ       grid (B, Kv, S/bq, T/bk), kv innermost.  dq = scale * sum dS k;
           also writes D = rowsum(dO * O) (f32) for the dK/dV kernel.
  dK/dV    grid (B, Kv, T/bk, S/bq), q innermost.  dv = sum P^T dO,
           dk = scale * sum dS^T q, summed over the group's heads in VMEM.

The backward kernels recompute P = exp(s - lse) from q, k and the saved
logsumexp.  No tensor of S x T elements reaches HBM in any of them.

Layout: (B, S, H, D) is viewed as (B, S, H*D) (a free reshape); the heads of
kv head j are lanes [j*G*D, (j+1)*G*D), and a head_dim that is a multiple
of 128 compiles for the TPU.  The logsumexp and D are (B, H, 1, S): a q
tile's values lie along lanes.  The dK/dV kernel works on transposed tiles
(kv rows, q columns), where they broadcast as they lie.

Precision: the MXU operands stay in the input dtype (bf16 on the train
path) and every product accumulates in f32.  Scores, the running max and
sum, the accumulators, P and dS are f32; P and dS are cast to the input
dtype only as MXU operands.

Masking: a tile with no live (q, k) pair does no work, and its index map
clamps the block it reads to the nearest live one, so the step names the
block already in VMEM and no DMA is issued.  Only tiles that straddle the
mask's edge build and apply the elementwise mask.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Tile sizes, from a sweep on a TPU v5e at granite-8b's shapes (GQA 32/8,
# head_dim 128; PERF.md section 5).  A grid step holds the scores of its G
# heads' q tiles, bq * G rows of bk, and each head's (bq, D) slab: at most
# SCORE_ROWS rows and HEAD_ELEMS elements (granite's at bq = 512) fit VMEM,
# so wider groups and heads take shorter q tiles, down to MIN_BLOCK.
BLOCK_Q = 512
BLOCK_K = 1024
SCORE_ROWS = 2048
HEAD_ELEMS = 512 * 128
MIN_BLOCK = 128
# Names of the three pallas_calls; each is a jax.named_scope of its ops.
KERNEL_NAMES = ("flash_attention_fwd", "flash_attention_dq",
                "flash_attention_dkv")

NT = (((1,), (1,)), ((), ()))     # a @ b.T
NN = (((1,), (0,)), ((), ()))     # a @ b


@dataclasses.dataclass(frozen=True)
class _Tiles:
    """Which (q tile, kv tile) pairs hold live positions.  Queries are
    right-aligned against the keys: query row i sits at position
    i + seq_k - seq_q.  Live: k <= q, and k > q - window when window > 0;
    with ``causal`` false every pair is live."""
    bq: int
    bk: int
    seq_q: int
    seq_k: int
    causal: bool
    window: int

    @property
    def off(self) -> int:
        return self.seq_k - self.seq_q

    @property
    def nq(self) -> int:
        return self.seq_q // self.bq

    @property
    def nk(self) -> int:
        return self.seq_k // self.bk

    def _bounds(self, iq, ik):
        fq = iq * self.bq + self.off
        fk = ik * self.bk
        return fq, fq + self.bq - 1, fk, fk + self.bk - 1

    def live(self, iq, ik):
        if not self.causal:
            return jnp.bool_(True)
        fq, lq, fk, lk = self._bounds(iq, ik)
        run = fk <= lq
        if self.window > 0:
            run &= lk > fq - self.window
        return run

    def straddles(self, iq, ik):
        """Some pair of the tile is masked."""
        if not self.causal:
            return jnp.bool_(False)
        fq, lq, fk, lk = self._bounds(iq, ik)
        cut = lk > fq
        if self.window > 0:
            cut |= lq - fk >= self.window
        return cut

    def k_block(self, iq, ik):
        """kv block to read at step ik of q tile iq: ik clamped to the live
        range, so dead steps re-name a block already in VMEM."""
        if not self.causal:
            return ik
        fq, lq, _, _ = self._bounds(iq, 0)
        hi = jnp.minimum(lq // self.bk, self.nk - 1)
        ik = jnp.minimum(ik, hi)
        if self.window > 0:
            lo = jnp.maximum(fq - self.window + 1, 0) // self.bk
            ik = jnp.maximum(ik, lo)
        return ik

    def q_block(self, ik, iq):
        """q block to read at step iq of kv tile ik, clamped likewise."""
        if not self.causal:
            return iq
        _, _, fk, lk = self._bounds(0, ik)
        lo = jnp.minimum(jnp.maximum(fk - self.off, 0) // self.bq,
                         self.nq - 1)
        if self.window > 0:
            last = jnp.maximum(lk + self.window - 1 - self.off, 0) // self.bq
            iq = jnp.minimum(iq, jnp.minimum(last, self.nq - 1))
        return jnp.maximum(iq, lo)

    def mask(self, iq, ik, shape, transposed=False):
        """Live pairs of tile (iq, ik) as a boolean array of ``shape``:
        (bq, bk), or (bk, bq) when ``transposed``."""
        qa, ka = (1, 0) if transposed else (0, 1)
        q_pos = iq * self.bq + self.off + \
            jax.lax.broadcasted_iota(jnp.int32, shape, qa)
        k_pos = ik * self.bk + jax.lax.broadcasted_iota(jnp.int32, shape, ka)
        m = k_pos <= q_pos
        if self.window > 0:
            m &= k_pos > q_pos - self.window
        return m

    def run(self, iq, ik, body):
        """``body(masked)`` on a live tile: masked only where it straddles."""
        live, cut = self.live(iq, ik), self.straddles(iq, ik)

        @pl.when(live & cut)
        def _edge():
            body(True)

        @pl.when(live & jnp.logical_not(cut))
        def _inside():
            body(False)


def _heads(group: int, d: int):
    """Lane slice of each query head of a group's (rows, G*D) tile."""
    return [slice(g * d, (g + 1) * d) for g in range(group)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, tiles: _Tiles, scale: float, group: int, d: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        live = tiles.mask(iq, ik, (tiles.bq, tiles.bk)) if masked else None
        for g, h in enumerate(_heads(group, d)):
            s = jax.lax.dot_general(q_ref[:, h], k, NT,
                                    preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(live, s, NEG_INF)
            m_prev = m_scr[g]                                  # (bq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[:, h] = acc_scr[:, h] * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, NN, preferred_element_type=jnp.float32)
            m_scr[g] = m_new

    tiles.run(iq, ik, body)

    @pl.when(ik == tiles.nk - 1)
    def _flush():
        for g, h in enumerate(_heads(group, d)):
            l = l_scr[g]
            o_ref[:, h] = (acc_scr[:, h] / l).astype(o_ref.dtype)
            lse_ref[g] = jnp.transpose(m_scr[g] + jnp.log(l))  # (1, bq)


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dsum_ref,
               d_scr, acc_scr, *, tiles: _Tiles, scale: float, group: int,
               d: int):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        for g, h in enumerate(_heads(group, d)):
            dsum = jnp.sum(o_ref[:, h].astype(jnp.float32)
                           * do_ref[:, h].astype(jnp.float32),
                           axis=1, keepdims=True)              # (bq, 1)
            d_scr[g] = dsum
            dsum_ref[g] = jnp.transpose(dsum)

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        live = tiles.mask(iq, ik, (tiles.bq, tiles.bk)) if masked else None
        for g, h in enumerate(_heads(group, d)):
            s = jax.lax.dot_general(q_ref[:, h], k, NT,
                                    preferred_element_type=jnp.float32) * scale
            p = jnp.exp(s - jnp.transpose(lse_ref[g]))
            if masked:
                p = jnp.where(live, p, 0.0)
            dp = jax.lax.dot_general(do_ref[:, h], v, NT,
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - d_scr[g])
            acc_scr[:, h] += jax.lax.dot_general(
                ds.astype(k.dtype), k, NN, preferred_element_type=jnp.float32)

    tiles.run(iq, ik, body)

    @pl.when(ik == tiles.nk - 1)
    def _flush():
        dq_ref[...] = (acc_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, tiles: _Tiles, scale: float,
                group: int, d: int):
    ik, iq = pl.program_id(2), pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def body(masked):
        k, v = k_ref[...], v_ref[...]
        live = (tiles.mask(iq, ik, (tiles.bk, tiles.bq), transposed=True)
                if masked else None)
        for g, h in enumerate(_heads(group, d)):
            q, do = q_ref[:, h], do_ref[:, h]
            st = scale * jax.lax.dot_general(
                k, q, NT, preferred_element_type=jnp.float32)
            pt = jnp.exp(st - lse_ref[g])                      # (bk, bq)
            if masked:
                pt = jnp.where(live, pt, 0.0)
            dv_scr[...] += jax.lax.dot_general(
                pt.astype(do.dtype), do, NN,
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, NT,
                                      preferred_element_type=jnp.float32)
            dst = pt * (dpt - dsum_ref[g])
            dk_scr[...] += jax.lax.dot_general(
                dst.astype(q.dtype), q, NN, preferred_element_type=jnp.float32)

    tiles.run(iq, ik, body)

    @pl.when(iq == tiles.nq - 1)
    def _flush():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def block_sizes(s: int, t: int, group: int, d: int, block_q: int = BLOCK_Q,
                block_k: int = BLOCK_K):
    """(bq, bk) for S queries, T keys, G query heads a kv head and head_dim
    D: bq is S where S is within every cap (``block_q``, SCORE_ROWS / G,
    HEAD_ELEMS / D), else the largest power of two within them."""
    cap = min(block_q, SCORE_ROWS // group, HEAD_ELEMS // d)
    bq = s if s <= cap else 1 << (cap.bit_length() - 1)
    return bq, min(block_k, t)


def fits(s: int, t: int, group: int, d: int, block_q: int = BLOCK_Q,
         block_k: int = BLOCK_K) -> bool:
    """The kernels take S queries and T keys, G query heads a kv head and
    head_dim D in Mosaic: D a whole number of 128 lanes, q tiles of at
    least MIN_BLOCK rows, and S and T whole numbers of tiles."""
    bq, bk = block_sizes(s, t, group, d, block_q, block_k)
    return d % 128 == 0 and bq >= MIN_BLOCK and s % bq == 0 and t % bk == 0


def _tiles(q, k, causal, window, block_q, block_k) -> _Tiles:
    # Only divisibility is checked: interpret mode takes any tile, and a
    # CPU lowering traces the Mosaic branch of ``ops`` too.
    s, t = q.shape[1], k.shape[1]
    bq, bk = block_sizes(s, t, q.shape[2] // k.shape[2], q.shape[3],
                         block_q, block_k)
    if s % bq or t % bk:
        raise ValueError(f"seq lens ({s},{t}) must divide blocks ({bq},{bk})")
    if causal and t < s:
        raise ValueError(f"causal attention needs seq_k {t} >= seq_q {s}")
    return _Tiles(bq, bk, s, t, causal, window)


PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, window: int = 0,
                        block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                        interpret: bool = False):
    """q: (B, S, H, D); k, v: (B, T, Kv, D) with H % Kv == 0.  Returns the
    output (B, S, H, D) in q.dtype and the logsumexp (B, H, 1, S) in f32."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    tiles = _tiles(q, k, causal, window, block_q, block_k)
    bq, bk = tiles.bq, tiles.bk

    q_spec = pl.BlockSpec((None, bq, g * d),
                          lambda ib, ikv, iq, ik: (ib, iq, ikv))
    kv_spec = pl.BlockSpec(
        (None, bk, d),
        lambda ib, ikv, iq, ik: (ib, tiles.k_block(iq, ik), ikv))
    row_spec = pl.BlockSpec((None, g, 1, bq),
                            lambda ib, ikv, iq, ik: (ib, ikv, 0, iq))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, tiles=tiles, scale=1 / math.sqrt(d),
                          group=g, d=d),
        grid=(b, kv, tiles.nq, tiles.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, g * d), jnp.float32)],
        compiler_params=PARAMS,
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(q.reshape(b, s, h * d), k.reshape(b, t, kv * d),
      v.reshape(b, t, kv * d))
    return out.reshape(b, s, h, d), lse


def flash_attention_bwd(q, k, v, o, lse, do, causal: bool = True,
                        window: int = 0, block_q: int = BLOCK_Q,
                        block_k: int = BLOCK_K, interpret: bool = False):
    """Gradients (dq, dk, dv) of ``flash_attention_fwd``'s output, given its
    output ``o``, its logsumexp ``lse`` and the output's cotangent ``do``."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    tiles = _tiles(q, k, causal, window, block_q, block_k)
    bq, bk = tiles.bq, tiles.bk
    scale = 1 / math.sqrt(d)
    q3, o3, do3 = (x.reshape(b, s, h * d) for x in (q, o, do))
    k3, v3 = k.reshape(b, t, kv * d), v.reshape(b, t, kv * d)

    q_spec = pl.BlockSpec((None, bq, g * d),
                          lambda ib, ikv, iq, ik: (ib, iq, ikv))
    kv_spec = pl.BlockSpec(
        (None, bk, d),
        lambda ib, ikv, iq, ik: (ib, tiles.k_block(iq, ik), ikv))
    row_spec = pl.BlockSpec((None, g, 1, bq),
                            lambda ib, ikv, iq, ik: (ib, ikv, 0, iq))
    dq, dsum = pl.pallas_call(
        functools.partial(_dq_kernel, tiles=tiles, scale=scale, group=g, d=d),
        grid=(b, kv, tiles.nq, tiles.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b, h, 1, s), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, bq, 1), jnp.float32),
                        pltpu.VMEM((bq, g * d), jnp.float32)],
        compiler_params=PARAMS,
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(q3, k3, v3, o3, do3, lse)

    q_spec = pl.BlockSpec(
        (None, bq, g * d),
        lambda ib, ikv, ik, iq: (ib, tiles.q_block(ik, iq), ikv))
    kv_spec = pl.BlockSpec((None, bk, d),
                           lambda ib, ikv, ik, iq: (ib, ik, ikv))
    row_spec = pl.BlockSpec(
        (None, g, 1, bq),
        lambda ib, ikv, ik, iq: (ib, ikv, 0, tiles.q_block(ik, iq)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, tiles=tiles, scale=scale, group=g,
                          d=d),
        grid=(b, kv, tiles.nk, tiles.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, t, kv * d), k.dtype),
                   jax.ShapeDtypeStruct((b, t, kv * d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=PARAMS,
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(q3, k3, v3, do3, lse, dsum)
    return (dq.reshape(b, s, h, d), dk.reshape(b, t, kv, d),
            dv.reshape(b, t, kv, d))
