"""Plain float32 reference of the decoder's training step.

Forward pass, loss, gradients and AdamW, written from the published
description of a pre-norm decoder (RoPE, RMSNorm or LayerNorm, an LM head
of its own or the embedding's transpose, mean token cross-entropy) in plain
``jax.numpy``.  Each layer is the block of its kind in the configuration's
``model.pattern``, from ``blocks/<kind>.py`` (``attn``: GQA attention and a
SwiGLU or tanh-GELU MLP; ``local``: the same with a sliding window); a kind
is added as one more file there, with no edit here.  It imports nothing of
the program and takes nothing the program made: the weights come from
``weights.init`` and the seed, the rows are the tokens the timed path was
fed.

Every matrix product runs at ``Precision.HIGHEST``.  ``low`` rounds both
operands of every product to a lower type first (per-tensor scaled, the
backward pass straight through): the control that a program computing in
that type has to fail.

Memory: attention runs over blocks of 512 queries and the LM head over
blocks of about 1024 tokens a device, each block rematerialised, every
layer rematerialised; the weights, gradients and AdamW moments are sharded
over every device given (FSDP), the rows over the same devices.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import blocks, weights
from chipbench.compare import leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
TOKENS_PER_HEAD_BLOCK = 1024


def _round(x, low):
    if low is None:
        return x
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(low).max), 1.0)
    q = (x / scale).astype(low).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(eq, a, b, low):
    return jnp.einsum(eq, _round(a, low), _round(b, low), precision=HIGHEST)


def _norm(p, x, m):
    eps = m["norm_eps"]
    if m["norm"] == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * p["scale"]


def _rope(x, theta):
    """Rotary embedding on (B, S, H, D), halves rotated as pairs
    (i, i + D/2)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _at(tree, path: str):
    for k in path.split("/"):
        tree = tree[k]
    return tree


def loss_sum(params, tokens, labels, m, low=None, rows_local=None):
    """Sum over every token of the cross-entropy of its label, plus the
    layers' extra loss once for each token."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0) * math.sqrt(m["d_model"])
    groups, scan, tail = blocks.slots(m)
    layers = [(kind, jax.tree_util.tree_map(lambda a, g=g: a[g],
                                            _at(params, path)))
              for g in range(groups) for path, kind in scan]
    layers += [(kind, _at(params, path)) for path, kind in tail]
    extra = jnp.zeros((), jnp.float32)
    for kind, lp in layers:
        apply = blocks.load(kind).apply
        x, e = jax.checkpoint(lambda lp, x, apply=apply: apply(lp, x, m, low))(
            lp, x)
        extra = extra + e
    x = _norm(params["final_norm"], x, m)

    tb = min(s, max(1, TOKENS_PER_HEAD_BLOCK // (rows_local or b)))
    while s % tb:
        tb //= 2
    w_head = (params["embed"].T if m["tie_embeddings"]
              else params["lm_head"])
    valid = jnp.arange(w_head.shape[1]) < m["vocab"]

    @jax.checkpoint
    def head(i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * tb, tb, axis=1)
        yi = jax.lax.dynamic_slice_in_dim(labels, i * tb, tb, axis=1)
        z = _mm("btd,dv->btv", xi, w_head, low)
        z = jnp.where(valid, z, -jnp.inf)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(z, yi[..., None], -1)[..., 0])

    return jnp.sum(jax.lax.map(head, jnp.arange(s // tb))) + extra * (b * s)


def adamw(p, g, mu, nu, t, hp):
    """One AdamW step on one leaf, at step number ``t`` (1-based); the decay
    is decoupled and applies to every leaf."""
    b1, b2 = hp["b1"], hp["b2"]
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * jnp.square(g)
    upd = (mu / (1 - b1 ** t)) / (jnp.sqrt(nu / (1 - b2 ** t)) + hp["eps"])
    return p - hp["lr"] * (upd + hp["weight_decay"] * p), mu, nu


def fsdp_shardings(tree, mesh: Mesh):
    """Each leaf split over every device along its largest dimension that
    they divide (never the stacked-layer axis of a ``scan/`` leaf; a
    ``tail/`` leaf is one layer); the rest replicated."""
    n = mesh.devices.size

    def leaf(path, x):
        stacked = path.startswith("scan/")
        dims = [i for i in range(x.ndim)
                if x.shape[i] % n == 0 and not (stacked and i == 0)]
        spec = [None] * x.ndim
        if dims and n > 1:
            spec[max(dims, key=lambda i: x.shape[i])] = "r"
        return NamedSharding(mesh, P(*spec))

    flat = weights.flatten(tree)
    return weights.nest({p: leaf(p, x) for p, x in flat.items()})


class Reference:
    """The reference on ``devices``: ``run(key, batches)`` follows the
    program's first ``len(batches)`` steps from the weights of ``key``."""

    def __init__(self, m: dict, hp: dict, devices: Sequence, batch: int,
                 low=None, rows: Optional[int] = None):
        self.m, self.hp, self.low = m, hp, low
        self.rows = rows or batch        # a fault may drop rows
        self.mesh = Mesh(np.array(list(devices)), ("r",))
        n = self.mesh.devices.size
        self.p_sh = fsdp_shardings(weights.shapes(m), self.mesh)
        rows_split = self.rows % n == 0
        self.b_sh = NamedSharding(self.mesh, P("r" if rows_split else None))
        rows_local = self.rows // n if rows_split else self.rows
        scalar = NamedSharding(self.mesh, P())

        def grad(params, tokens, labels):
            total, g = jax.value_and_grad(loss_sum)(
                params, tokens, labels, m, low, rows_local)
            count = tokens.size
            return total / count, jax.tree_util.tree_map(
                lambda x: x / count, g)

        def step(params, mu, nu, g, t):
            out = jax.tree_util.tree_map(
                lambda p, g, a, b: adamw(p, g, a, b, t, hp),
                params, g, mu, nu)
            pick = [jax.tree_util.tree_map(
                lambda o, k=k: o[k], out,
                is_leaf=lambda o: isinstance(o, tuple)) for k in range(3)]
            return tuple(pick)

        self._init = jax.jit(lambda k: weights.init(k, m),
                             out_shardings=self.p_sh)
        self._grad = jax.jit(grad, in_shardings=(self.p_sh, self.b_sh,
                                                 self.b_sh),
                             out_shardings=(scalar, self.p_sh))
        self._step = jax.jit(step, donate_argnums=(0, 1, 2),
                             out_shardings=(self.p_sh,) * 3)
        self._norms = jax.jit(leaf_norms)
        self._delta_norms = jax.jit(
            lambda p, k: leaf_norms(jax.tree_util.tree_map(
                jnp.subtract, p, weights.init(k, m))))

    def run(self, key, batches) -> dict:
        """``batches``: [(tokens, labels)] as host arrays, one per step.
        Returns the loss of each step, each leaf's gradient norm at step 1
        and each leaf's norm of the change after the last step."""
        params = self._init(key)
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for t, (tokens, labels) in enumerate(batches, start=1):
            tokens = jax.device_put(tokens[: self.rows], self.b_sh)
            labels = jax.device_put(labels[: self.rows], self.b_sh)
            loss, g = self._grad(params, tokens, labels)
            losses.append(float(loss))
            if t == 1:
                grad_norms = np.asarray(self._norms(g))
            params, mu, nu = self._step(params, mu, nu, g, float(t))
            del g
        delta = np.asarray(self._delta_norms(params, key))
        del params, mu, nu
        return {"losses": losses, "grad_norms": grad_norms,
                "delta_norms": delta}
