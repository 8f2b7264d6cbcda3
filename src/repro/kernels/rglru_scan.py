"""Pallas TPU kernel for the RG-LRU linear recurrence (chunked scan).

Computes h_t = a_t * h_{t-1} + b_t (zero initial state) over the sequence
axis with explicit VMEM tiling:

  grid = (batch, R // block_r, S // block_s)   [sequence chunks innermost]

The recurrence carry ``h`` lives in VMEM scratch and is threaded across
sequence-chunk grid steps (TPU grids execute sequentially); it is reset at
chunk 0 of every (batch, r-block) pair.  Inside a chunk, a ``fori_loop``
walks the chunk one sublane tile (8 rows of f32, 16 of bf16) at a time:
the tile is read from the refs at an aligned offset, its rows are stepped
with static slices, and the tile of h is stored back whole.  HBM traffic
is exactly one read of (a, b) and one write of h.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_scr, *, block_s: int, tile: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(i, h):
        rows = pl.ds(pl.multiple_of(i * tile, tile), tile)
        a = a_ref[rows, :].astype(jnp.float32)   # (tile, block_r)
        b = b_ref[rows, :].astype(jnp.float32)
        out = []
        for t in range(tile):
            h = a[t:t + 1] * h + b[t:t + 1]
            out.append(h)
        o_ref[rows, :] = jnp.concatenate(out, axis=0).astype(o_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, block_s // tile, step, h_scr[...])


def rglru_scan_fwd(a: jnp.ndarray, b: jnp.ndarray, block_s: int = 256,
                   block_r: int = 128, interpret: bool = False) -> jnp.ndarray:
    """a, b: (B, S, R) -> h: (B, S, R) (same dtype as b)."""
    bsz, s, r = a.shape
    bs = min(block_s, s)
    br = min(block_r, r)
    tile = 32 // max(jnp.dtype(a.dtype).itemsize, jnp.dtype(b.dtype).itemsize)
    if s % bs or r % br or bs % tile:
        raise ValueError(f"(S={s}, R={r}) must divide blocks ({bs},{br}), "
                         f"and {bs} the sublane tile {tile}")
    grid = (bsz, r // br, s // bs)
    kernel = functools.partial(_rglru_kernel, block_s=bs, tile=tile)
    spec = pl.BlockSpec((None, bs, br), lambda ib, ir, ic: (ib, ic, ir))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, r), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, br), jnp.float32)],
        interpret=interpret,
    )(a, b)
