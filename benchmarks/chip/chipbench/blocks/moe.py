"""Block kind ``moe``: pre-norm causal self-attention over the whole
sequence (GQA; RoPE with the YaRN scaling of ``model.yarn`` where the file
states one), then a pre-norm routed expert FFN over the block of experts
this chip holds, each added to the residual.

The router (``model.moe``) scores all ``num_experts`` with a softmax; each
token takes its ``top_k`` and renormalises their gates to sum to one.  The
chip holds experts ``first_held`` .. ``first_held + held - 1`` and adds, for
each token, ``gate * SwiGLU_e(x)`` over those of its experts that it holds:
the part of the layer's result that the chips holding the others would add
is left out, as the program leaves it out.  Every held expert runs on every
token, weighted by the token's gate for it (zero where the expert is not
among the token's picks): no capacity, no sort, no kernel.  The extra loss
is the load-balancing loss, ``aux_coef * E * sum_e(mean prob_e *
assignments_e / T)``, and the z-loss, ``router_z_coef * mean(logsumexp^2)``,
both over all the router's experts.

YaRN (Peng et al. 2023, as the HF rope utilities compute it): pair i's
frequency ``theta^(-2i/d)`` is kept below the correction range and divided
by ``factor`` above it, with a linear ramp between; the range runs from
``floor(c(beta_fast))`` to ``ceil(c(beta_slow))``, ``c(r) = d ln(L0 / (2 pi
r)) / (2 ln theta)``; cos and sin are scaled by ``attention_factor``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import weights
from chipbench.blocks import attn
from chipbench.reference import Q_BLOCK, _mm, _norm


def held(m: dict) -> int:
    return m["moe"].get("held") or m["moe"]["num_experts"]


def layout(m: dict) -> dict:
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"]
    e, n, f = m["moe"]["num_experts"], held(m), m["moe"]["d_expert"]
    return {**weights.norm_layout("norm1", m),
            **weights.norm_layout("norm2", m),
            "attn/wq": ((d, h, hd), 1 / math.sqrt(d)),
            "attn/wk": ((d, kv, hd), 1 / math.sqrt(d)),
            "attn/wv": ((d, kv, hd), 1 / math.sqrt(d)),
            "attn/wo": ((h, hd, d), 1 / math.sqrt(h * hd)),
            "moe/router/w": ((d, e), 1 / math.sqrt(d)),
            "moe/experts/wi": ((n, d, f), 1 / math.sqrt(d)),
            "moe/experts/wg": ((n, d, f), 1 / math.sqrt(d)),
            "moe/experts/wo": ((n, f, d), 1 / math.sqrt(f))}


def _rope(x, theta, yarn):
    """Rotary embedding on (B, S, H, D), halves rotated as pairs
    (i, i + D/2), with YaRN's frequencies and scale when ``yarn``."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    scale = 1.0
    if yarn:
        def c(r):
            return (d * math.log(yarn["original_max_position"]
                                 / (2 * math.pi * r)) / (2 * math.log(theta)))
        low = max(math.floor(c(yarn["beta_fast"])), 0)
        high = min(math.ceil(c(yarn["beta_slow"])), d - 1)
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / yarn["factor"] * ramp + inv * (1.0 - ramp)
        scale = yarn["attention_factor"]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = scale * jnp.cos(ang)[None, :, None, :]
    sin = scale * jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(p, x, m, low, window=0):
    """Causal GQA attention; a query sees the keys less than ``window``
    positions behind it when ``window`` > 0 (plain rope), every earlier key
    otherwise (rope with the file's YaRN scaling)."""
    b, s, _ = x.shape
    h, kv, hd = m["n_heads"], m["n_kv"], m["head_dim"]
    g = h // kv
    yarn = None if window else m.get("yarn")
    q = _rope(_mm("bsd,dhk->bshk", x, p["wq"], low), m["rope_theta"], yarn)
    k = _rope(_mm("bsd,dhk->bshk", x, p["wk"], low), m["rope_theta"], yarn)
    v = _mm("bsd,dhk->bshk", x, p["wv"], low)
    qg = q.reshape(b, s, kv, g, hd)
    qb = min(s, Q_BLOCK)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=1)
        sc = _mm("bqkgd,btkd->bkgqt", qi, k, low) / math.sqrt(hd)
        pos = (i * qb + jnp.arange(qb))[:, None]
        live = jnp.arange(s)[None, :] <= pos
        if window > 0:
            live = live & (jnp.arange(s)[None, :] > pos - window)
        w = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
        return _mm("bkgqt,btkd->bqkgd", w, v, low).reshape(b, qb, h, hd)

    out = jax.lax.map(block, jnp.arange(s // qb))       # (n, B, qb, H, D)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)
    return _mm("bshk,hkd->bsd", out, p["wo"], low)


def experts(p, x, m, low):
    """(the held experts' part of the FFN output, aux + z loss)."""
    c = m["moe"]
    e, k, n, first = c["num_experts"], c["top_k"], held(m), \
        c.get("first_held", 0)
    b, s, d = x.shape
    t = x.reshape(b * s, d)
    logits = _mm("td,de->te", t, p["router"]["w"], low)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    picks = jax.nn.one_hot(idx, e, dtype=jnp.float32)          # (T, k, E)
    weight = jnp.einsum("tk,tke->te", gate, picks)[:, first:first + n]
    counts = jnp.sum(picks, axis=(0, 1))
    extra = (c.get("aux_coef", 1e-2) * e
             * jnp.sum(jnp.mean(probs, axis=0) * counts / t.shape[0])
             + c.get("router_z_coef", 1e-3) * jnp.mean(jnp.square(
                 jax.scipy.special.logsumexp(logits, axis=-1))))
    w = p["experts"]

    @jax.checkpoint
    def one(acc, expert):
        wi, wg, wo, gate_e = expert
        hi = _mm("td,df->tf", t, wi, low)
        gi = _mm("td,df->tf", t, wg, low)
        yi = _mm("tf,fd->td", jax.nn.silu(gi) * hi, wo, low)
        return acc + gate_e[:, None] * yi, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(t),
                          (w["wi"], w["wg"], w["wo"], weight.T))
    return out.reshape(b, s, d), extra


def apply(p, x, m, low, window=0):
    x = x + attention(p["attn"], _norm(p["norm1"], x, m), m, low, window)
    y, extra = experts(p["moe"], _norm(p["norm2"], x, m), m, low)
    return x + y, extra


def matmul_params(m: dict) -> int:
    """Attention, the router, and the held experts' expected share of a
    token: top_k * held / num_experts experts' worth."""
    d, h, kv, hd = m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"]
    c = m["moe"]
    expert = 3 * d * c["d_expert"]
    return (d * h * hd + 2 * d * kv * hd + h * hd * d + d * c["num_experts"]
            + expert * c["top_k"] * held(m) // c["num_experts"])


def score_flops_per_token(m: dict, seq_len: int) -> int:
    """Scores and their weighted sum, over every key (PaLM's count)."""
    return attn.score_flops_per_token(m, seq_len)


def expert_products(m: dict, batch: int, seq_len: int):
    """FLOPs and HBM bytes of one layer's grouped products in a train step,
    at the expected ``batch * seq_len * top_k * held / num_experts`` rows
    (R) landing on the held experts.  Each of the three products, (R, K) by
    the held experts' (K, N), runs forward, again in the rematerialised
    forward when ``remat``, and twice in the backward (input and weight
    gradients): 2 R K N FLOPs each time, and its operands and result read
    or written once, 2 (R K + held K N + R N) bytes in bfloat16."""
    c, d, f, n = m["moe"], m["d_model"], m["moe"]["d_expert"], held(m)
    rows = batch * seq_len * c["top_k"] * n / c["num_experts"]
    runs = 4 if m["remat"] else 3
    shapes = ((d, f), (d, f), (f, d))                 # wi, wg, wo
    return (runs * sum(2 * rows * a * b for a, b in shapes),
            runs * sum(2 * (rows * a + n * a * b + rows * b)
                       for a, b in shapes))
