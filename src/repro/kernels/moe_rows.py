"""Pallas TPU kernels that move an MoE layer's rows between token order and
expert order, touching only the live rows.

The layer sorts its (token, expert) assignments by expert into a buffer of
R rows: first the ``live`` assignments to the experts it holds, then rows
that no product reads.  Three kernels move rows across that boundary:

  stage_rows     an (N, D) array, over its row tiles that hold a live row,
                 as words one DMA each can fetch (below).
  dispatch_rows  token order -> expert order.  Row r < live of the result
                 is ``scale[r] * src[index[r]]``.  The grid runs over the
                 live row tiles alone (a dynamic grid), so the tiles past
                 ``live`` are never written; the tile that straddles it is
                 written with zeros past ``live``.  Given ``ys`` it also
                 returns ``<src[index[r]], ys[r]>`` for each live row (the
                 gate's cotangent, in expert order), from the rows it
                 already holds.
  combine_rows   expert order -> token order.  Token t of the result is
                 ``sum_j weight[t, j] * src[slot[t, j]]`` over its
                 assignments with ``slot[t, j] >= 0``, accumulated in
                 float32.

Each row moves with one DMA, every DMA of a grid step in flight before the
first wait.  The TPU's HBM tiles a 2-D array by 8 rows, so no DMA fetches a
single row of one, while an (N, 1, D) array of 32-bit words is tiled by
single rows.  ``stage_rows`` writes that: bfloat16 rows in pairs, row 2i in
the low and row 2i + 1 in the high half of each word (float32 rows one a
word), and the kernels fetch a row's word row and take its half.  Values
move unrounded; products and sums are float32; results are cast to the
output dtype once.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Sorted rows a grid step of dispatch_rows and stage_rows (R is a whole
# number of them, or of 128), and tokens a grid step of combine_rows.
ROW_TILE = 256
TOKEN_TILE = 128
# Rows of one vector pass: a column of per-row scalars is built from, and a
# row of them written to, 128 lanes at a time.
CHUNK = 128
KERNEL_NAMES = ("dispatch_rows", "combine_rows", "stage_rows")
PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                              vmem_limit_bytes=64 * 2**20)


def packing(dtype) -> int:
    """Rows a staged word row holds: 2 of bfloat16, 1 of float32."""
    dtype = jnp.dtype(dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        raise ValueError(f"rows of {dtype} cannot be staged")
    return 4 // dtype.itemsize


def row_tile(rows: int) -> int:
    return math.gcd(rows, ROW_TILE)


def _live_tiles(live: jnp.ndarray, tile: int) -> jnp.ndarray:
    """Row tiles holding a live row; the first one always (it is written
    with zeros when nothing is live)."""
    return jnp.maximum((live + tile - 1) // tile, 1)


def _eye(n: int) -> jnp.ndarray:
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _column(row: jnp.ndarray) -> jnp.ndarray:
    """(1, n) -> (n, 1) float32, exact: the diagonal of the row broadcast
    down."""
    n = row.shape[1]
    row = jnp.broadcast_to(row.astype(jnp.float32), (n, n))
    return jnp.sum(jnp.where(_eye(n), row, 0.0), axis=1, keepdims=True)


def _row(col: jnp.ndarray) -> jnp.ndarray:
    """(n, 1) -> (1, n) float32, exact."""
    n = col.shape[0]
    col = jnp.broadcast_to(col, (n, n))
    return jnp.sum(jnp.where(_eye(n), col, 0.0), axis=0, keepdims=True)


def _unpack(words: jnp.ndarray, half: jnp.ndarray | None) -> jnp.ndarray:
    """Staged words (n, D) uint32 -> float32 rows; ``half`` (n, 1) is 1
    where a row is a pair's high half, None for float32 rows."""
    if half is not None:
        words = jnp.where(half == 1, words & jnp.uint32(0xFFFF0000),
                          words << 16)
    return jax.lax.bitcast_convert_type(words, jnp.float32)


def _wait_rows(src, buf, sem, n):
    """Wait for ``n`` DMAs of one of ``src``'s word rows on ``sem``."""
    def wait(_, c):
        pltpu.make_async_copy(src.at[0], buf.at[0], sem).wait()
        return c
    jax.lax.fori_loop(0, n, wait, 0)


# ---------------------------------------------------------------------------
# stage_rows
# ---------------------------------------------------------------------------


def _stage_kernel(live_ref, x_ref, out_ref):
    del live_ref
    out_ref[:, 0, :] = pltpu.bitcast(x_ref[...], jnp.uint32)


def stage_rows(x: jnp.ndarray, live: jnp.ndarray | int, *,
               interpret: bool = False) -> jnp.ndarray:
    """x (N, D) bfloat16 or float32 -> (N / packing, 1, D) uint32 words,
    written over the row tiles that hold one of the first ``live`` rows;
    the rest are not written.  N is padded to a whole tile of 16."""
    p = packing(x.dtype)
    rows, d = x.shape
    if rows % 16:
        x = jnp.pad(x, ((0, 16 - rows % 16), (0, 0)))
        rows = x.shape[0]
    tile = row_tile(rows)
    return pl.pallas_call(
        _stage_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_live_tiles(jnp.asarray(live), tile),),
            in_specs=[pl.BlockSpec((tile, d), lambda i, live: (i, 0))],
            out_specs=pl.BlockSpec((tile // p, 1, d),
                                   lambda i, live: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows // p, 1, d), jnp.uint32),
        compiler_params=PARAMS,
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=2 * rows * d * 4 // p),
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(jnp.reshape(live, (1,)).astype(jnp.int32), x)


# ---------------------------------------------------------------------------
# dispatch_rows
# ---------------------------------------------------------------------------


def _dispatch_kernel(live_ref, index_ref, index_row, src, *refs, tile, p,
                     scaled, dot):
    refs = list(refs)
    scale_ref = refs.pop(0) if scaled else None
    ys_ref = refs.pop(0) if dot else None
    out_ref = refs.pop(0)
    dot_ref = refs.pop(0) if dot else None
    buf, sem = refs
    i = pl.program_id(0)
    n = jnp.clip(live_ref[0] - i * tile, 0, tile)

    def start(r, c):
        pltpu.make_async_copy(src.at[index_ref[0, r] >> (p - 1)],
                              buf.at[r], sem).start()
        return c

    jax.lax.fori_loop(0, n, start, 0)
    _wait_rows(src, buf, sem, n)
    chunk = min(CHUNK, tile)
    for c in range(0, tile, chunk):
        half = None
        if p > 1:
            half = _column(index_row[:, c:c + chunk] & 1).astype(jnp.int32)
        rows = _unpack(buf[c:c + chunk, 0, :], half)
        keep = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) + c < n
        out = rows
        if scaled:
            out = rows * _column(scale_ref[:, c:c + chunk])
        out_ref[c:c + chunk, :] = jnp.where(keep, out, 0.0).astype(
            out_ref.dtype)
        if dot:
            d = jnp.sum(rows * ys_ref[c:c + chunk, :].astype(jnp.float32),
                        axis=1, keepdims=True)
            dot_ref[:, c:c + chunk] = _row(jnp.where(keep, d, 0.0))


def dispatch_rows(src: jnp.ndarray, index: jnp.ndarray, live: jnp.ndarray,
                  scale: jnp.ndarray | None = None,
                  ys: jnp.ndarray | None = None, *, dtype,
                  interpret: bool = False):
    """src: ``stage_rows`` of the (T, D) rows, in ``dtype``; index (R,)
    int32, the source row of each sorted row; live () int32.  Returns (R,
    D) in ``dtype``: row r < live is ``scale[r] * src[index[r]]`` (scale
    (R,) float32, or 1), zero from ``live`` to the end of its tile,
    unwritten past that.  With ``ys`` (R, D) also (R,) float32,
    ``<src[index[r]], ys[r]>`` on the written rows."""
    p = packing(dtype)
    rows, d = index.shape[0], src.shape[-1]
    tile = row_tile(rows)
    tiles = rows // tile
    index = index.astype(jnp.int32).reshape(tiles, 1, tile)
    blocked = pl.BlockSpec((None, 1, tile), lambda i, live: (i, 0, 0))
    in_specs = [pl.BlockSpec((None, 1, tile), lambda i, live: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                blocked, pl.BlockSpec(memory_space=pl.ANY)]
    args = [index, index, src]
    if scale is not None:
        in_specs.append(blocked)
        args.append(scale.astype(jnp.float32).reshape(tiles, 1, tile))
    row_block = pl.BlockSpec((tile, d), lambda i, live: (i, 0))
    out_specs = [row_block]
    out_shape = [jax.ShapeDtypeStruct((rows, d), dtype)]
    if ys is not None:
        in_specs.append(row_block)
        args.append(ys)
        out_specs.append(blocked)
        out_shape.append(jax.ShapeDtypeStruct((tiles, 1, tile), jnp.float32))
    moved = 4 * d + jnp.dtype(dtype).itemsize * d * (2 if ys is not None
                                                      else 1)
    out = pl.pallas_call(
        functools.partial(_dispatch_kernel, tile=tile, p=p,
                          scaled=scale is not None, dot=ys is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(_live_tiles(live, tile),),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((tile, 1, d), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=out_shape,
        compiler_params=PARAMS,
        cost_estimate=pl.CostEstimate(flops=2 * rows * d, transcendentals=0,
                                      bytes_accessed=rows * moved),
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(jnp.reshape(live, (1,)).astype(jnp.int32), *args)
    if ys is None:
        return out[0]
    return out[0], out[1].reshape(rows)


# ---------------------------------------------------------------------------
# combine_rows
# ---------------------------------------------------------------------------


def _combine_kernel(count_ref, depth_ref, fetch_ref, place_ref, slot_ref,
                    weight_ref, src, out_ref, buf, acc, sem, *, tile, k, p):
    i = pl.program_id(0)
    n = count_ref[i]

    def start(r, c):
        pltpu.make_async_copy(src.at[fetch_ref[0, r] >> (p - 1)],
                              buf.at[place_ref[0, r]], sem).start()
        return c

    jax.lax.fori_loop(0, n, start, 0)
    _wait_rows(src, buf, sem, n)
    acc[...] = jnp.zeros(acc.shape, jnp.float32)
    for q in range(k):
        @pl.when(q < depth_ref[i])
        def _():
            w = weight_ref[:, q:q + 1]
            half = slot_ref[:, q:q + 1] & 1 if p > 1 else None
            rows = _unpack(buf[q * tile:(q + 1) * tile, 0, :], half)
            acc[...] += jnp.where(w != 0, w * rows, 0.0)
    out_ref[...] = acc[...].astype(out_ref.dtype)


def combine_rows(src: jnp.ndarray, slot: jnp.ndarray, weight: jnp.ndarray,
                 *, dtype, interpret: bool = False) -> jnp.ndarray:
    """src: ``stage_rows`` of the (R, D) sorted rows, in ``dtype``; slot
    (T, k) int32, each assignment's sorted row or -1; weight (T, k)
    float32, zero where slot is -1.  Returns (T, D) in ``dtype``: token t
    is the float32 sum of ``weight[t, j] * src[slot[t, j]]`` over its j
    with a row.

    Sorts (no gathers) first move each token's rows to its front, and
    each token tile's (row, buffer place) pairs to the tile's front: a
    grid step issues one DMA a row and adds as many (tile, D) slabs as
    its fullest token has rows (``depth``), not k."""
    p = packing(dtype)
    t, k = slot.shape
    d = src.shape[-1]
    tile = min(TOKEN_TILE, -(-t // 8) * 8)
    padded = -(-t // tile) * tile
    tiles = padded // tile
    slot = slot.astype(jnp.int32)
    weight = weight.astype(jnp.float32)
    if padded != t:
        slot = jnp.pad(slot, ((0, padded - t), (0, 0)), constant_values=-1)
        weight = jnp.pad(weight, ((0, padded - t), (0, 0)))
    _, slot, weight = jax.lax.sort(((slot < 0).astype(jnp.int32), slot,
                                    weight), dimension=1, num_keys=1,
                                   is_stable=True)
    has = slot >= 0
    per_token = jnp.sum(has, axis=1, dtype=jnp.int32).reshape(tiles, tile)
    place = (jnp.arange(k, dtype=jnp.int32)[None, :] * tile
             + jnp.arange(padded, dtype=jnp.int32)[:, None] % tile)
    flat = (tiles, tile * k)
    _, fetch, place = jax.lax.sort(
        ((~has).astype(jnp.int32).reshape(flat), slot.reshape(flat),
         place.reshape(flat)), dimension=1, num_keys=1, is_stable=True)
    pairs = pl.BlockSpec((None, 1, tile * k), lambda i, n, q: (i, 0, 0),
                         memory_space=pltpu.SMEM)
    block = pl.BlockSpec((tile, k), lambda i, n, q: (i, 0))
    out = pl.pallas_call(
        functools.partial(_combine_kernel, tile=tile, k=k, p=p),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[pairs, pairs, block, block,
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile, d), lambda i, n, q: (i, 0)),
            scratch_shapes=[pltpu.VMEM((k * tile, 1, d), jnp.uint32),
                            pltpu.VMEM((tile, d), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct((padded, d), dtype),
        compiler_params=PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * padded * k * d, transcendentals=0,
            bytes_accessed=padded * d * (4 + jnp.dtype(dtype).itemsize)),
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(jnp.sum(per_token, axis=1), jnp.max(per_token, axis=1),
      fetch.reshape(tiles, 1, tile * k), place.reshape(tiles, 1, tile * k),
      slot, weight, src)
    return out[:t]
