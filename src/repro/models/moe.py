"""Mixture-of-Experts FFN: routed top-k experts, dropless, over the block of
experts this layer holds (+ shared experts).

The router scores every routed expert (softmax over ``num_experts``); each
token takes its ``top_k`` and renormalises their gates to sum to one.  A
layer holds a contiguous block of the experts, ``MoEConfig.held`` from
``first_held`` (all of them by default): under expert parallelism, one
chip's share.  It computes, for every (token, expert) assignment whose
expert it holds, ``gate * SwiGLU_e(x)``, and nothing for the others, whose
part of the result the chips holding them add.

No token is dropped.  The assignments are sorted by expert into a buffer
that holds every assignment that can land here, ``T * min(k, held)`` rows,
and the held experts run as grouped matrix products over their own rows of
it (``_grouped``).  The load-balancing and z-losses are computed over all
the router's experts.

Rows move between token order and that buffer in two ways.  On one device
the Pallas row kernels (``kernels.ops.moe_dispatch``, ``moe_combine``)
touch only the live rows, the assignments to held experts, and never form
a (T k, d) array; rows past them are left unwritten, and no product reads
them.  A mesh of several devices keeps the gathers over the whole buffer
(``_gather_rows``), since a Mosaic kernel has no partitioning rule.

Covers the registry's MoE archs:
  * deepseek-moe-16b — 64 routed top-6 + 2 shared experts (fine-grained);
  * arctic-480b — 128 routed top-2 + parallel dense residual FFN
    (``dense_residual_ff``; handled by the caller in transformer.py);
  * mellum2-12b — 64 routed top-8, sliding and full attention layers.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import Params, apply_mlp, dense_init, init_mlp, one_device, scoped

HIGHEST = jax.lax.Precision.HIGHEST
# The grouped products' tiles: rows, then the contracted and output widths,
# each the largest multiple of 128 up to this that divides the width.
ROW_TILE = 512
WIDTH_TILE = 1024


def init_moe(key, cfg: ModelConfig) -> Params:
    assert cfg.moe is not None
    m = cfg.moe
    d, f, n = cfg.d_model, cfg.d_expert_eff, m.n_held
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": {"w": dense_init(ks[0], (d, m.num_experts))},
        "experts": {
            "wi": dense_init(ks[1], (n, d, f)),
            "wg": dense_init(ks[2], (n, d, f)),
            "wo": dense_init(ks[3], (n, f, d)),
        },
    }
    if m.num_shared > 0:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=f * m.num_shared)
    return p


def _width_tile(dim: int) -> int:
    if dim <= WIDTH_TILE:
        return dim
    for t in range(WIDTH_TILE, 127, -128):
        if dim % t == 0:
            return t
    return WIDTH_TILE


def _tiling(m: int, k: int, n: int) -> Tuple[int, int, int]:
    return min(ROW_TILE, m), _width_tile(k), _width_tile(n)


def _grouped(x: jnp.ndarray, w: jnp.ndarray,
             sizes: jnp.ndarray) -> jnp.ndarray:
    """Rows of ``x`` (R, K), sorted by expert, times their expert's matrix
    of ``w`` (n, K, N).  ``sizes`` (n + 1,) counts the rows of each held
    expert, then the rows past them, which come out zero.

    One device: the Pallas grouped matmul (megablox ``gmm``, Mosaic on a
    TPU lowering, interpreted on the CPU), whose grid visits only the held
    experts' row tiles.  A mesh of several devices takes
    ``lax.ragged_dot``, since a Mosaic kernel has no partitioning rule: it
    gets a group for every row, the rows past the held experts' times a
    zero matrix, and accumulates in float32 as the kernel does."""
    if not one_device():
        w = jnp.concatenate([w, jnp.zeros((1,) + w.shape[1:], w.dtype)])
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32
                                  ).astype(x.dtype)
    # imported only here: Pallas adds a second to the start of a process
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def run(x, w, sizes, interpret):
        return gmm(x, w, sizes, x.dtype, _tiling, None, None, False,
                   interpret)

    return jax.lax.platform_dependent(
        x, w, sizes,
        cpu=lambda x, w, sizes: run(x, w, sizes, True),
        default=lambda x, w, sizes: run(x, w, sizes, False))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_rows(src: jnp.ndarray, idx: jnp.ndarray, inv: jnp.ndarray,
                 rep: int) -> jnp.ndarray:
    """Row p of the result is ``src[idx[p] // rep]``, zero where that lies
    past ``src``.  ``inv`` inverts ``idx`` (``idx[inv[i]] == i`` wherever
    ``inv[i]`` is a row of the result), so the transpose is a gather too and
    no scatter runs: row q of ``src`` gets the sum of the cotangents of the
    ``rep`` rows that read it, ``inv[q * rep + j]``."""
    return jnp.take(src, idx // rep, axis=0, mode="fill", fill_value=0)


def _gather_rows_fwd(src, idx, inv, rep):
    return _gather_rows(src, idx, inv, rep), (inv, src.shape[0])


def _gather_rows_bwd(rep, res, dout):
    inv, n = res
    dsrc = jnp.take(dout, inv, axis=0, mode="fill", fill_value=0)
    return dsrc.reshape(n, rep, -1).sum(axis=1), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def _buffer_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of the sorted buffer: every assignment that can land on the
    held experts (a token's k experts are distinct), padded to whole row
    tiles."""
    m = cfg.moe
    rows = tokens * min(m.top_k, m.n_held)
    tile = min(ROW_TILE, -(-rows // 16) * 16)
    return -(-rows // tile) * tile


def _sort_by_expert(idx: jnp.ndarray, m):
    """The (T, k) assignments of ``idx`` sorted by expert, the held ones
    first: ``order`` (T k,), the assignment (token * k + j) at each sorted
    position; ``slot`` (T k,), each assignment's position, its inverse;
    ``held`` (T k,).  Both by sorts: a scatter or gather of T k elements
    costs more on a TPU."""
    t, k = idx.shape
    local = idx.reshape(-1) - m.first_held
    held = (local >= 0) & (local < m.n_held)
    iota = jnp.arange(t * k, dtype=jnp.int32)
    order = jax.lax.sort((jnp.where(held, local, m.n_held), iota),
                         num_keys=1, is_stable=True)[1]
    slot = jax.lax.sort((order, iota), num_keys=1)[1]
    return order, slot, held


@scoped("moe")
def apply_moe(p: Params, x: jnp.ndarray,
              cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (out, aux).

    aux: ``aux_loss`` (load-balancing, Shazeer-style) and ``z_loss`` over
    all the router's experts, and ``expert_counts`` (held,): the
    assignments each held expert received.
    """
    m = cfg.moe
    b, s, d = x.shape
    t, k, n = b * s, m.top_k, m.n_held
    dt = x.dtype
    xt = x.reshape(t, d)
    kernels = one_device()
    if kernels:
        # imported only here: Pallas adds a second to the start of a process
        from repro.kernels import ops

    with jax.named_scope("router"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            p["router"]["w"], precision=HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        z = jax.scipy.special.logsumexp(logits, axis=-1)
        z_loss = m.router_z_coef * jnp.mean(jnp.square(z))
        gate, idx = jax.lax.top_k(probs, k)                  # (T, k)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        counts = jnp.sum(jax.nn.one_hot(idx, m.num_experts,
                                        dtype=jnp.float32), axis=(0, 1))
        aux_loss = m.aux_coef * m.num_experts * jnp.sum(
            jnp.mean(probs, axis=0) * counts / t)

    with jax.named_scope("dispatch"):
        rows = _buffer_rows(cfg, t)
        order, slot, held = _sort_by_expert(idx, m)
        held_counts = counts[m.first_held:m.first_held + n].astype(jnp.int32)
        live = jnp.sum(held_counts)
        sizes = jnp.concatenate([held_counts, (rows - live)[None]])
        if kernels:
            slot = jnp.where(held, slot, -1).reshape(t, k)
        else:
            # the buffer's row r holds assignment order[r], or none (t * k)
            order = jnp.pad(order, (0, max(rows - t * k, 0)),
                            constant_values=t * k)[:rows]

    with jax.named_scope("experts"):
        wi, wg, wo = (p["experts"][w].astype(dt) for w in ("wi", "wg", "wo"))

    def first_products(xt, wi, wg):
        with jax.named_scope("dispatch"):
            if kernels:
                xs = ops.moe_dispatch(xt, order, live, slot, rows)
            else:
                xs = _gather_rows(xt, order, slot, k)        # (rows, d)
        with jax.named_scope("experts"):
            return _grouped(xs, wi, sizes), _grouped(xs, wg, sizes)

    if kernels:
        # the backward dispatches again for the weight gradients, so the
        # (rows, d) rows are not held through the layer's backward
        first_products = jax.checkpoint(first_products)
    h, g = first_products(xt, wi, wg)
    with jax.named_scope("experts"):
        ys = _grouped(jax.nn.silu(g) * h, wo, sizes)

    with jax.named_scope("combine"):
        if kernels:
            out = ops.moe_combine(ys, gate, order, live, slot)
        else:
            y = _gather_rows(ys, slot, order, 1)             # (T k, d)
            y = jnp.where(held[:, None], y, 0).reshape(t, k, d)
            out = jnp.einsum("tkd,tk->td", y, gate.astype(dt),
                             preferred_element_type=jnp.float32).astype(dt)

    out = out.reshape(b, s, d)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, cfg)
    aux = {"aux_loss": aux_loss, "z_loss": z_loss,
           "expert_counts": held_counts}
    return out, aux
