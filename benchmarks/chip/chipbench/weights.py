"""Weights and random keys made from ``--seed``, in the layout the train step
takes.

The benchmark makes the weights itself, on the device, in one jitted call,
so that the reference can make the very same ones from the seed without
taking anything from the program.  The layout is the program's parameter
pytree for a dense decoder whose layer pattern is one ``attn`` block: the
layers stacked on a leading axis under ``scan/s0_attn``.  ``run.py`` checks
it against the program's own ``param_shapes`` before any step runs.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

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


def layout(m: dict) -> dict:
    """{path: (shape, std or 'ones' / 'zeros')} for the model dict ``m`` of
    a configuration file."""
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"],
                       m["d_ff"])
    L, V = m["n_layers"], padded_vocab(m)
    ln = m["norm"] == "layernorm"

    def norm(prefix, stack):
        lead = (L,) if stack else ()
        out = {f"{prefix}/scale": (lead + (d,), "ones")}
        if ln:
            out[f"{prefix}/bias"] = (lead + (d,), "zeros")
        return out

    blk = "scan/s0_attn"
    out = {"embed": ((V, d), 0.02)}
    if not m["tie_embeddings"]:          # tied: the head is embed.T
        out["lm_head"] = ((d, V), 1 / math.sqrt(d))
    out.update(norm("final_norm", False))
    out.update(norm(f"{blk}/norm1", True))
    out.update(norm(f"{blk}/norm2", True))
    out.update({
        f"{blk}/attn/wq": ((L, d, h, hd), 1 / math.sqrt(d)),
        f"{blk}/attn/wk": ((L, d, kv, hd), 1 / math.sqrt(d)),
        f"{blk}/attn/wv": ((L, d, kv, hd), 1 / math.sqrt(d)),
        f"{blk}/attn/wo": ((L, h, hd, d), 1 / math.sqrt(h * hd)),
        f"{blk}/mlp/wi": ((L, d, f), 1 / math.sqrt(d)),
        f"{blk}/mlp/wo": ((L, f, d), 1 / math.sqrt(f)),
    })
    if m["mlp"] in ("swiglu", "geglu"):
        out[f"{blk}/mlp/wg"] = ((L, d, f), 1 / math.sqrt(d))
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
