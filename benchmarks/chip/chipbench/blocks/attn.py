"""Block kind ``attn``: pre-norm causal self-attention over the whole
sequence (GQA, RoPE), then a pre-norm MLP, each added to the residual."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.reference import Q_BLOCK, _mm, _norm, _rope


def layout(m: dict) -> dict:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"],
                       m["d_ff"])
    out = {**weights.norm_layout("norm1", m), **weights.norm_layout("norm2", m),
           "attn/wq": ((d, h, hd), 1 / math.sqrt(d)),
           "attn/wk": ((d, kv, hd), 1 / math.sqrt(d)),
           "attn/wv": ((d, kv, hd), 1 / math.sqrt(d)),
           "attn/wo": ((h, hd, d), 1 / math.sqrt(h * hd)),
           "mlp/wi": ((d, f), 1 / math.sqrt(d)),
           "mlp/wo": ((f, d), 1 / math.sqrt(f))}
    if m["mlp"] in ("swiglu", "geglu"):
        out["mlp/wg"] = ((d, f), 1 / math.sqrt(d))
    return out


def _attention(p, x, m, low, window=0):
    """Causal attention; with ``window`` > 0 a query sees only the keys
    less than ``window`` positions behind it, as ``layers.causal_mask``."""
    b, s, _ = x.shape
    h, kv, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    g = h // kv
    q = _rope(_mm("bsd,dhk->bshk", x, p["wq"], low), m["rope_theta"])
    k = _rope(_mm("bsd,dhk->bshk", x, p["wk"], low), m["rope_theta"])
    v = _mm("bsd,dhk->bshk", x, p["wv"], low)
    qg = q.reshape(b, s, kv, g, hd)
    qb = min(s, Q_BLOCK)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=1)
        sc = _mm("bqkgd,btkd->bkgqt", qi, k, low) / math.sqrt(hd)
        pos = (i * qb + jnp.arange(qb))[:, None]
        causal = jnp.arange(s)[None, :] <= pos
        if window > 0:
            causal = causal & (jnp.arange(s)[None, :] > pos - window)
        sc = jnp.where(causal, sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return _mm("bkgqt,btkd->bqkgd", w, v, low).reshape(b, qb, h, hd)

    out = jax.lax.map(block, jnp.arange(s // qb))       # (n, B, qb, H, D)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    return _mm("bshk,hkd->bsd", out, p["wo"], low)


def _mlp(p, x, m, low):
    hi = _mm("bsd,df->bsf", x, p["wi"], low)
    if m["mlp"] == "swiglu":
        hi = jax.nn.silu(_mm("bsd,df->bsf", x, p["wg"], low)) * hi
    elif m["mlp"] == "gelu":
        hi = jax.nn.gelu(hi, approximate=True)
    else:
        raise ValueError(f"mlp {m['mlp']!r} not in the reference")
    return _mm("bsf,fd->bsd", hi, p["wo"], low)


def apply(p, x, m, low, window=0):
    x = x + _attention(p["attn"], _norm(p["norm1"], x, m), m, low, window)
    x = x + _mlp(p["mlp"], _norm(p["norm2"], x, m), m, low)
    return x, jnp.zeros((), jnp.float32)


def matmul_params(m: dict) -> int:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"],
                       m["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    return attn + (3 if m["mlp"] in ("swiglu", "geglu") else 2) * d * f


def score_flops_per_token(m: dict, seq_len: int) -> int:
    """Scores and their weighted sum, over every key (PaLM's count)."""
    return 12 * m["n_heads"] * m["head_dim"] * seq_len
