"""Step builders: train / prefill / serve, plus their sharding pytrees.

These are the functions the dry-run lowers and the drivers execute. Each
builder returns a pure function suitable for ``jax.jit`` with explicit
in/out shardings on the production mesh.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.optim.optimizers import Optimizer
from repro.parallel.sharding import (ShardingRules, decode_state_shardings,
                                     params_shardings, use_mesh)


def make_train_step(cfg: ModelConfig, optimizer: Optimizer,
                    mesh: Optional[Mesh] = None,
                    rules: Optional[ShardingRules] = None):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def train_step(params, opt_state, batch):
        with use_mesh(mesh, rules):
            (loss, metrics), grads = jax.value_and_grad(
                transformer.loss_fn, has_aux=True)(params, batch, cfg)
            with jax.named_scope("optimizer"):
                new_params, new_opt = optimizer.update(grads, opt_state,
                                                       params)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step


def make_grad_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                   rules: Optional[ShardingRules] = None):
    """(params, batch) -> (grads, metrics); used by async/compressed DP."""

    def grad_step(params, batch):
        with use_mesh(mesh, rules):
            (loss, metrics), grads = jax.value_and_grad(
                transformer.loss_fn, has_aux=True)(params, batch, cfg)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return grads, metrics

    return grad_step


def make_prefill_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                      rules: Optional[ShardingRules] = None):
    """(params, batch) -> logits (inference forward, no grad)."""

    def prefill_step(params, batch):
        with use_mesh(mesh, rules):
            logits, _ = transformer.forward(params, batch, cfg)
        return logits

    return prefill_step


def make_serve_step(cfg: ModelConfig, mesh: Optional[Mesh] = None,
                    rules: Optional[ShardingRules] = None):
    """(params, state, token) -> (logits, state): one decode step."""

    def serve_step(params, state, token):
        with use_mesh(mesh, rules):
            return transformer.serve_step(params, state, token, cfg)

    return serve_step


# ---------------------------------------------------------------------------
# Sharding pytrees for jit in_shardings
# ---------------------------------------------------------------------------


def batch_shardings(batch_specs: Dict, mesh: Mesh,
                    rules: Optional[ShardingRules] = None):
    rules = rules or ShardingRules()
    axes = rules.resolve("batch", mesh)

    def leaf(x):
        if getattr(x, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        n = 1
        for a in (axes or ()):
            n *= mesh.shape[a]
        if axes is None or x.shape[0] % n != 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(axes, *([None] * (x.ndim - 1))))

    return jax.tree_util.tree_map(leaf, batch_specs)


def opt_state_shardings(opt_state_shapes, mesh: Mesh,
                        rules: Optional[ShardingRules] = None):
    """Optimizer state mirrors parameter sharding (suffix-matched rules)."""
    return params_shardings(opt_state_shapes, mesh, rules)


def train_in_shardings(cfg: ModelConfig, optimizer: Optimizer,
                       batch_specs: Dict, mesh: Mesh,
                       rules: Optional[ShardingRules] = None):
    pshapes = transformer.param_shapes(cfg)
    oshapes = jax.eval_shape(optimizer.init, pshapes)
    return (params_shardings(pshapes, mesh, rules),
            opt_state_shardings(oshapes, mesh, rules),
            batch_shardings(batch_specs, mesh, rules)), pshapes, oshapes


def jit_train_step(cfg: ModelConfig, optimizer: Optimizer, in_shardings,
                   mesh: Mesh, rules: Optional[ShardingRules] = None):
    """The train step jitted on ``mesh``: params and optimizer state keep
    their ``train_in_shardings`` layout across steps (and are donated);
    the metrics come back replicated."""
    p_sh, o_sh, _ = in_shardings
    return jax.jit(make_train_step(cfg, optimizer, mesh, rules),
                   in_shardings=in_shardings,
                   out_shardings=(p_sh, o_sh, NamedSharding(mesh, P())),
                   donate_argnums=(0, 1))


def serve_in_shardings(cfg: ModelConfig, state_shapes, token_batch: int,
                       mesh: Mesh,
                       rules: Optional[ShardingRules] = None):
    rules = rules or ShardingRules()
    pshapes = transformer.param_shapes(cfg)
    axes = rules.resolve("batch", mesh)
    n = 1
    for a in (axes or ()):
        n *= mesh.shape[a]
    token_sh = (NamedSharding(mesh, P(axes))
                if axes and token_batch % n == 0
                else NamedSharding(mesh, P()))
    return (params_shardings(pshapes, mesh, rules),
            decode_state_shardings(state_shapes, mesh, rules),
            token_sh), pshapes
