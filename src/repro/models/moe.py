"""Mixture-of-Experts FFN: shared experts + routed top-k with capacity.

Mesh-TensorFlow/T5X-style einsum dispatch: tokens are split into groups of
``group_size``; within a group each token picks its top-k experts, positions
are assigned up to a per-expert capacity ``C = ceil(G * k * cf / E)``, and
dispatch/combine are dense einsums (MXU-friendly, shardable: the expert dim
partitions over the ``model`` axis => the resharding between the token and
expert layouts lowers to an all-to-all on TPU).

Covers both assigned MoE archs:
  * deepseek-moe-16b — 64 routed top-6 + 2 shared experts (fine-grained);
  * arctic-480b — 128 routed top-2 + parallel dense residual FFN
    (``dense_residual_ff``; handled by the caller in transformer.py).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard
from .config import ModelConfig
from .layers import Params, apply_mlp, dense_init, init_mlp, scoped


def init_moe(key, cfg: ModelConfig) -> Params:
    assert cfg.moe is not None
    m = cfg.moe
    d, f = cfg.d_model, cfg.d_expert_eff
    ks = jax.random.split(key, 5)
    p: Params = {
        "router": {"w": dense_init(ks[0], (d, m.num_experts))},
        "experts": {
            "wi": dense_init(ks[1], (m.num_experts, d, f)),
            "wg": dense_init(ks[2], (m.num_experts, d, f)),
            "wo": dense_init(ks[3], (m.num_experts, f, d)),
        },
    }
    if m.num_shared > 0:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=f * m.num_shared)
    return p


def capacity(cfg: ModelConfig, group: int) -> int:
    m = cfg.moe
    c = int(math.ceil(group * m.top_k * m.capacity_factor / m.num_experts))
    return max(c, 1)


@scoped("moe")
def apply_moe(p: Params, x: jnp.ndarray,
              cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """x: (B, S, d) -> (out, aux_losses).

    aux: ``aux_loss`` (load-balancing, Shazeer-style) and ``z_loss``.
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    g = min(m.group_size, t)
    n_groups = max(t // g, 1)
    g = t // n_groups  # exact split (t divisible in all our shapes)
    xg = x.reshape(n_groups, g, d)

    logits = jnp.einsum("ngd,de->nge", xg, p["router"]["w"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)

    # aux losses (computed over all tokens)
    z = jax.scipy.special.logsumexp(logits, axis=-1)
    z_loss = m.router_z_coef * jnp.mean(jnp.square(z))
    me = jnp.mean(probs.reshape(-1, m.num_experts), axis=0)

    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)   # (n, g, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    cap = capacity(cfg, g)
    # one-hot expert assignment per (token, k): (n, g, k, E)
    assign = jax.nn.one_hot(gate_idx, m.num_experts, dtype=jnp.float32)
    ce = jnp.mean(jnp.sum(assign, axis=2).reshape(-1, m.num_experts), axis=0)
    aux_loss = m.aux_coef * m.num_experts * jnp.sum(me * ce)

    # position within each expert's buffer, k-major then token order
    # (n, g*k, E) flattened so ranks interleave across k slots correctly
    assign_fl = assign.transpose(0, 2, 1, 3).reshape(n_groups, -1,
                                                     m.num_experts)
    pos = jnp.cumsum(assign_fl, axis=1) * assign_fl - 1.0   # (n, g*k, E)
    keep = (pos >= 0) & (pos < cap)
    pos = jnp.where(keep, pos, 0.0)
    onehot_pos = jax.nn.one_hot(pos.astype(jnp.int32), cap,
                                dtype=jnp.float32) * keep[..., None]
    # back to (n, k, g, E, C) -> (n, g, k, E, C)
    disp = onehot_pos.reshape(n_groups, m.top_k, g, m.num_experts, cap)
    disp = disp.transpose(0, 2, 1, 3, 4)
    combine = disp * gate_vals[..., None, None]              # weighted
    dispatch = jnp.sum(disp, axis=2)                         # (n, g, E, C)
    combine = jnp.sum(combine, axis=2)                       # (n, g, E, C)

    dt = x.dtype
    spec = "moe_ecd_grouped" if m.dispatch_local else "moe_ecd"
    expert_in = jnp.einsum("ngd,ngec->necd", xg,
                           dispatch.astype(dt))              # (n, E, C, d)
    expert_in = shard(expert_in, spec)
    w = p["experts"]
    h = jnp.einsum("necd,edf->necf", expert_in, w["wi"].astype(dt))
    gte = jnp.einsum("necd,edf->necf", expert_in, w["wg"].astype(dt))
    h = jax.nn.silu(gte) * h
    eout = jnp.einsum("necf,efd->necd", h, w["wo"].astype(dt))
    # NOTE(§Perf iter 2, REFUTED): re-sharding eout back to group-local
    # before the combine made XLA all-gather the expert outputs (340 GB) —
    # worse than the all-reduce it removed. Keep the expert layout here.
    eout = shard(eout, spec)
    out = jnp.einsum("necd,ngec->ngd", eout, combine.astype(dt))

    out = out.reshape(b, s, d)
    if "shared" in p:
        out = out + apply_mlp(p["shared"], x, cfg)
    aux = {"aux_loss": aux_loss, "z_loss": z_loss,
           "expert_load": me}
    return out, aux
