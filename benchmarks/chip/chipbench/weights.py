"""Weights and random keys made from ``--seed``, in the layout the train step
takes.

The benchmark makes the weights itself, on the device, in one jitted call,
so that the reference can make the very same ones from the seed without
taking anything from the program.  The layout is the program's parameter
pytree: the embedding, the LM head unless it is tied, the final norm, and
each layer's leaves as its kind's ``blocks/<kind>.py`` lays them out, under
``scan/s{i}_{kind}`` stacked over the pattern's groups and ``tail/t{i}_{kind}``
for the remainder.  A new layer kind adds its file there and edits nothing
here.  ``harness.Program`` checks the layout against the program's own
``param_shapes`` before any step runs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import blocks

# The program pads the vocabulary to a multiple of this, so the LM head
# shards over the tensor-parallel axis; logits past ``vocab`` are masked.
VOCAB_PAD = 512


def seed_key(seed: int):
    """A key for any whole ``seed``, also one wider than 32 bits
    (``PRNGKey`` keeps only the low 32 bits of an int)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def padded_vocab(m: dict) -> int:
    return -(-m["vocab"] // VOCAB_PAD) * VOCAB_PAD


def norm_layout(prefix: str, m: dict) -> dict:
    """A norm's leaves: a scale, and a bias for LayerNorm."""
    out = {f"{prefix}/scale": ((m["d_model"],), "ones")}
    if m["norm"] == "layernorm":
        out[f"{prefix}/bias"] = ((m["d_model"],), "zeros")
    return out


def layout(m: dict) -> dict:
    """{path: (shape, std or 'ones' / 'zeros')} for the model dict ``m`` of
    a configuration file."""
    d, V = m["d_model"], padded_vocab(m)
    out = {"embed": ((V, d), 0.02)}
    if not m["tie_embeddings"]:          # tied: the head is embed.T
        out["lm_head"] = ((d, V), 1 / math.sqrt(d))
    out.update(norm_layout("final_norm", m))
    groups, scan, tail = blocks.slots(m)
    for slots, lead in ((scan, (groups,)), (tail, ())):
        for prefix, kind in slots:
            for path, (shape, std) in blocks.load(kind).layout(m).items():
                out[f"{prefix}/{path}"] = (lead + tuple(shape), std)
    return out


def nest(flat: dict) -> dict:
    """{'a/b': x} -> {'a': {'b': x}}."""
    tree: dict = {}
    for path, x in flat.items():
        node = tree
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = x
    return tree


def flatten(tree, prefix: str = "") -> dict:
    """Inverse of :func:`nest`, paths in sorted order."""
    out = {}
    for k in sorted(tree):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(flatten(tree[k], path))
        else:
            out[path] = tree[k]
    return out


def shapes(m: dict) -> dict:
    return nest({p: jax.ShapeDtypeStruct(s, jnp.float32)
                 for p, (s, _) in layout(m).items()})


def init(key, m: dict) -> dict:
    """f32 weights in the program's layout from the seed's ``key``; pure,
    so one jitted call makes them on the device."""
    key = jax.random.fold_in(key, 1)
    out = {}
    for i, (path, (shape, std)) in enumerate(sorted(layout(m).items())):
        if std == "ones":
            out[path] = jnp.ones(shape, jnp.float32)
        elif std == "zeros":
            out[path] = jnp.zeros(shape, jnp.float32)
        else:
            out[path] = std * jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
    return nest(out)
