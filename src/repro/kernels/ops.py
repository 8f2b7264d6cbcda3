"""jit'd public wrappers around the Pallas kernels.

The kernels are compiled with Mosaic when the program is lowered for a TPU
and run in Pallas interpret mode when it is lowered for the CPU (the test
path).  The choice is made per lowering platform (``_on_platform``), so no
TPU program ever carries an interpreted kernel; a caller that has made that
choice itself passes ``interpret=False``.

``flash_attention``'s custom VJP runs the Pallas backward kernels (dQ and
dK/dV) on the forward's output and logsumexp.  ``rglru_scan``'s backward is
an associative scan of the adjoint recurrence.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

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
