"""jit'd public wrappers around the Pallas kernels.

The kernels are compiled with Mosaic when the program is lowered for a TPU
and run in Pallas interpret mode when it is lowered for the CPU (the test
path).  The choice is made per lowering platform (``_on_platform``), so no
TPU program ever carries an interpreted kernel; a caller that has made that
choice itself passes ``interpret=False``.

``flash_attention``'s custom VJP runs the Pallas backward kernels (dQ and
dK/dV) on the forward's output and logsumexp.  ``rglru_scan``'s backward is
an associative scan of the adjoint recurrence.  ``moe_dispatch`` and
``moe_combine`` move an MoE layer's live rows between token and expert
order (``moe_rows``); each one's transpose is the other's kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import moe_rows
from .flash_attention import flash_attention_bwd, flash_attention_fwd
from .rglru_scan import rglru_scan_fwd


def _on_platform(kernel_fwd, *args, **kw):
    """Interpret on the CPU, compile everywhere else."""
    return jax.lax.platform_dependent(
        *args,
        cpu=lambda *a: kernel_fwd(*a, interpret=True, **kw),
        default=lambda *a: kernel_fwd(*a, interpret=False, **kw))


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    interpret=None):
    """Fused attention with the Pallas kernels forward and backward.
    ``interpret`` None: Mosaic on a TPU lowering, interpreted on the CPU;
    False: Mosaic, for a caller that has already chosen the TPU (each
    kernel is then traced once, not once a platform)."""
    if interpret is None:
        return _on_platform(
            lambda q, k, v, interpret: _flash(q, k, v, causal, window,
                                              interpret), q, k, v)
    return _flash(q, k, v, causal, window, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, interpret):
    return _fa_fwd(q, k, v, causal, window, interpret)[0]


def _fa_fwd(q, k, v, causal, window, interpret):
    o, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                 interpret=interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, window, interpret, res, do):
    return flash_attention_bwd(*res, do, causal=causal, window=window,
                               interpret=interpret)


_flash.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------


@jax.custom_vjp
def rglru_scan(a, b):
    """h_t = a_t h_{t-1} + b_t over axis -2; a, b: (..., S, R)."""
    shape = a.shape
    a2 = a.reshape((-1,) + shape[-2:])
    b2 = b.reshape((-1,) + shape[-2:])
    h = _on_platform(rglru_scan_fwd, a2, b2)
    return h.reshape(shape)


def _rg_fwd(a, b):
    h = rglru_scan(a, b)
    return h, (a, h)


def _rg_bwd(res, g):
    a, h = res
    # reverse-time adjoint of the linear recurrence:
    #   lam_t = g_t + a_{t+1} * lam_{t+1};  db = lam;  da_t = lam_t * h_{t-1}
    a_next = jnp.concatenate(
        [a[..., 1:, :], jnp.zeros_like(a[..., :1, :])], axis=-2)

    def comb(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    lam_rev = jax.lax.associative_scan(
        comb, (jnp.flip(a_next, axis=-2), jnp.flip(g, axis=-2)), axis=-2)[1]
    lam = jnp.flip(lam_rev, axis=-2)
    h_prev = jnp.concatenate(
        [jnp.zeros_like(h[..., :1, :]), h[..., :-1, :]], axis=-2)
    return lam * h_prev, lam


rglru_scan.defvjp(_rg_fwd, _rg_bwd)


# ---------------------------------------------------------------------------
# MoE dispatch and combine
# ---------------------------------------------------------------------------
#
# ``order`` (T k,) int32: the assignment (token * k + j) at each sorted
# position, the held ones first; ``live`` () int32: how many are held;
# ``slot`` (T, k) int32: each assignment's row of the sorted buffer, -1
# where its expert is not held.  Rows past ``live`` are read by no one, and
# the kernels leave the whole tiles past it unwritten.  Values move between
# assignment and row order by sorts: a gather or scatter of T k elements
# costs more on a TPU.


def _first(v, n):
    """The first n entries of v, zeros past its end."""
    return jnp.pad(v, (0, max(n - v.shape[0], 0)))[:n]


def _to_rows(slot, values, rows):
    """values (T, k) at their assignments' rows (rows,): the held ones are
    rows 0..live-1, in slot order."""
    key = slot.reshape(-1)
    key = jnp.where(key >= 0, key, key.shape[0] + rows)
    return _first(jax.lax.sort((key, values.reshape(-1)), num_keys=1)[1],
                  rows)


def _to_assignments(order, values):
    """values (rows,) of the sorted rows at their assignments (T k,)."""
    return jax.lax.sort((order, _first(values, order.shape[0])),
                        num_keys=1)[1]


def _dispatch(x, order, live, k, rows):
    def run(x, index, live, interpret):
        src = moe_rows.stage_rows(x, x.shape[0], interpret=interpret)
        return moe_rows.dispatch_rows(src, index, live, dtype=x.dtype,
                                      interpret=interpret)
    return _on_platform(run, x, _first(order, rows) // k, live)


def _combine(ys, slot, weight, live):
    def run(ys, slot, weight, live, interpret):
        src = moe_rows.stage_rows(ys, live, interpret=interpret)
        return moe_rows.combine_rows(src, slot, weight, dtype=ys.dtype,
                                     interpret=interpret)
    return _on_platform(run, ys, slot, weight, live)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def moe_dispatch(x, order, live, slot, rows):
    """x (T, D) -> (rows, D): row r < live is x's row of assignment
    ``order[r]``."""
    return _dispatch(x, order, live, slot.shape[1], rows)


def _md_fwd(x, order, live, slot, rows):
    return _dispatch(x, order, live, slot.shape[1], rows), (live, slot)


def _md_bwd(rows, res, dxs):
    live, slot = res
    dx = _combine(dxs, slot, (slot >= 0).astype(jnp.float32), live)
    return dx, None, None, None


moe_dispatch.defvjp(_md_fwd, _md_bwd)


@jax.custom_vjp
def moe_combine(ys, gate, order, live, slot):
    """ys (R, D), gate (T, k) float32 -> (T, D): token t is the float32
    sum of ``gate[t, j] * ys[slot[t, j]]`` over its held assignments, in
    ``ys.dtype``."""
    return _combine(ys, slot, jnp.where(slot >= 0, gate, 0.0), live)


def _mc_fwd(ys, gate, order, live, slot):
    out = _combine(ys, slot, jnp.where(slot >= 0, gate, 0.0), live)
    return out, (ys, gate, order, live, slot)


def _mc_bwd(res, dout):
    ys, gate, order, live, slot = res
    rows, k = ys.shape[0], slot.shape[1]

    def run(dout, index, live, scale, ys, interpret):
        src = moe_rows.stage_rows(dout, dout.shape[0], interpret=interpret)
        return moe_rows.dispatch_rows(src, index, live, scale, ys,
                                      dtype=ys.dtype, interpret=interpret)

    dys, dot = _on_platform(run, dout, _first(order, rows) // k, live,
                            _to_rows(slot, gate, rows), ys)
    dgate = _to_assignments(order, dot).reshape(slot.shape)
    return dys, jnp.where(slot >= 0, dgate, 0.0), None, None, None


moe_combine.defvjp(_mc_fwd, _mc_bwd)
