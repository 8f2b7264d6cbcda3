"""Shared neural-net primitives for the model zoo (pure JAX, functional).

All parameters are plain pytrees (nested dicts of jnp arrays). Activation
sharding is injected through :func:`repro.parallel.sharding.shard` so the
same model code runs unsharded on CPU and fully partitioned on the
production mesh.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.parallel.sharding import current_mesh, role_size, shard
from .config import ModelConfig, YarnConfig

Params = Dict[str, jnp.ndarray]


def scoped(name: str):
    """Run the decorated function under ``jax.named_scope(name)``.

    Each layer kind has one such name (embed, norm, attention, mlp, moe,
    recurrent, lm_head, optimizer).  It reaches the HLO ``op_name`` of the
    layer's operations, through ``jvp``/``transpose`` and rematerialisation,
    so a device trace can put time down to the layer; the compiled
    instructions do not change.  ``jax.named_scope`` is looked up at each
    call, so a test can compile the same step without scopes.
    """
    def wrap(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run
    return wrap


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = -2) -> jnp.ndarray:
    fan_in = shape[in_axis]
    std = 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, dtype=jnp.float32) * std)


def embed_init(key, shape) -> jnp.ndarray:
    return jax.random.normal(key, shape, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ModelConfig, dim: Optional[int] = None) -> Params:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


@scoped("norm")
def apply_norm(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6)
        y = y * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + 1e-6) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn: YarnConfig) -> jnp.ndarray:
    """YaRN's blend of interpolated and original frequencies, as the HF
    rope utilities' ``_compute_yarn_parameters`` (truncated correction
    range): pair i keeps ``1 / theta^(2i/d)`` below the correction range,
    takes it divided by ``factor`` above it, and a linear ramp between."""
    def corr_dim(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               yarn: Optional[YarnConfig] = None) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S).  With
    ``yarn``, YaRN's frequencies, and cos and sin scaled by its
    ``attention_factor``."""
    d = x.shape[-1]
    freqs = (rope_freqs(d, theta) if yarn is None
             else yarn_freqs(d, theta, yarn))          # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                     # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d, f = cfg.d_model, (d_ff or cfg.d_ff)
    ks = jax.random.split(key, 3)
    if cfg.mlp in ("swiglu", "geglu"):
        return {"wi": dense_init(ks[0], (d, f)),
                "wg": dense_init(ks[1], (d, f)),
                "wo": dense_init(ks[2], (f, d))}
    return {"wi": dense_init(ks[0], (d, f)),
            "wo": dense_init(ks[2], (f, d))}


@scoped("mlp")
def apply_mlp(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    dt = x.dtype
    h = jnp.einsum("...d,df->...f", x, p["wi"].astype(dt))
    if cfg.mlp == "swiglu":
        g = jnp.einsum("...d,df->...f", x, p["wg"].astype(dt))
        h = jax.nn.silu(g) * h
    elif cfg.mlp == "geglu":
        g = jnp.einsum("...d,df->...f", x, p["wg"].astype(dt))
        h = jax.nn.gelu(g, approximate=True) * h
    else:
        h = jax.nn.gelu(h, approximate=True)
    h = shard(h, "act_ff")
    return jnp.einsum("...f,fd->...d", h, p["wo"].astype(dt))


# ---------------------------------------------------------------------------
# Attention (GQA / MQA, causal, sliding-window, cross)
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ModelConfig, cross: bool = False) -> Params:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    ks = jax.random.split(key, 5)
    p = {"wq": dense_init(ks[0], (d, h, hd), in_axis=0),
         "wk": dense_init(ks[1], (d, kv, hd), in_axis=0),
         "wv": dense_init(ks[2], (d, kv, hd), in_axis=0),
         "wo": dense_init(ks[3], (h, hd, d), in_axis=0)}
    if cross:
        # tanh-gated residual (Llama-3.2-Vision cross-attention layers)
        p["gate"] = jnp.zeros((), jnp.float32)
    return p


def _qkv(p: Params, x: jnp.ndarray, kv_src: jnp.ndarray):
    dt = x.dtype
    q = jnp.einsum("...sd,dhk->...shk", x, p["wq"].astype(dt))
    k = jnp.einsum("...sd,dhk->...shk", kv_src, p["wk"].astype(dt))
    v = jnp.einsum("...sd,dhk->...shk", kv_src, p["wv"].astype(dt))
    return q, k, v


def _shard_q(q: jnp.ndarray) -> jnp.ndarray:
    """Tensor-parallel over heads when they divide the TP axis; otherwise
    sequence-parallel (odd-head archs: whisper 12H, phi4 24H, starcoder 36H,
    arctic 56H, recurrentgemma 10H)."""
    if q.shape[-2] % max(role_size("tp"), 1) == 0:
        return shard(q, "act_heads")
    return shard(q, "act_heads_seq")


def _shard_kv(t: jnp.ndarray) -> jnp.ndarray:
    if t.shape[-2] % max(role_size("tp"), 1) == 0:
        return shard(t, "act_kv_heads")
    return shard(t, "act_kv")


def mha_logits_to_out(q, k, v, mask, cfg: ModelConfig,
                      softcap: float = 0.0) -> jnp.ndarray:
    """Grouped-query attention core. q: (B,S,H,D); k,v: (B,T,Kv,D).

    mask: broadcastable to (B, 1, S, T) boolean (True = attend) or None.
    """
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    qg = q.reshape(b, s, kvh, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k) / math.sqrt(d)
    score_dt = jnp.dtype(cfg.scores_dtype) if cfg is not None \
        else jnp.float32
    logits = logits.astype(score_dt)
    # sharding of the O(S*T) score tensor propagates from q (heads when the
    # head count divides the TP axis, else sequence — see _shard_q)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    if mask is not None:
        m = mask[:, :, None, :, :] if mask.ndim == 4 else mask
        logits = jnp.where(m, logits,
                           jnp.asarray(jnp.finfo(score_dt).min / 2,
                                       score_dt))
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, d)


def chunked_attention(q, k, v, cfg: ModelConfig, causal: bool = True,
                      window: int = 0) -> jnp.ndarray:
    """Online-softmax attention over kv chunks (flash semantics, pure JAX).

    Never materializes the full (S, T) score tensor: peak score memory is
    (S, chunk).  This is the dry-run-measurable form of the Pallas kernel
    (kernels/flash_attention.py implements the same schedule with explicit
    VMEM tiles); used by the memory-bound hillclimbs.
    """
    b, s, h, d = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    c = min(cfg.attention_chunk, t)
    n_chunks = t // c
    if t % c:
        raise ValueError(f"kv len {t} must divide chunk {c}")
    qg = q.reshape(b, s, kvh, g, d).astype(jnp.float32)
    scale = 1.0 / math.sqrt(d)
    kc = k.reshape(b, n_chunks, c, kvh, d).astype(jnp.float32)
    vc = v.reshape(b, n_chunks, c, kvh, d).astype(jnp.float32)
    q_pos = jnp.arange(s) + (t - s)

    def body(carry, inp):
        m_run, l_run, acc = carry
        kci, vci, ci = inp
        logits = jnp.einsum("bskgd,bckd->bkgsc", qg, kci) * scale
        k_pos = ci * c + jnp.arange(c)
        if causal:
            mask = k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            logits = jnp.where(mask[None, None, None], logits, -1e30)
        m_new = jnp.maximum(m_run, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m_run - m_new)
        l_new = l_run * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bkgsc,bckd->bkgsd", p, vci)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kvh, g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kvh, g, s), jnp.float32)
    a0 = jnp.zeros((b, kvh, g, s, d), jnp.float32)
    ks = jnp.moveaxis(kc, 1, 0)
    vs = jnp.moveaxis(vc, 1, 0)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (ks, vs, jnp.arange(n_chunks)))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    out = jnp.moveaxis(out.reshape(b, kvh * g, s, d), 1, 2)
    return out.astype(q.dtype)


def causal_mask(s: int, t: int, window: int = 0,
                offset: int = 0) -> jnp.ndarray:
    """(1, 1, s, t) boolean mask. ``offset`` = absolute position of query 0
    minus position of key 0 (for decode: offset = cache position)."""
    qi = jnp.arange(s)[:, None] + offset
    ki = jnp.arange(t)[None, :]
    m = ki <= qi
    if window > 0:
        m = m & (ki > qi - window)
    return m[None, None]


def one_device() -> bool:
    """No mesh, or a mesh of one device: where a Pallas kernel may run as
    it is (Mosaic kernels have no partitioning rule)."""
    mc = current_mesh()
    return mc is None or mc.mesh.size == 1


# Shortest sequence at which the fused kernel beats the naive score chain
# on a TPU v5e, forward, remat recompute and backward (chip sweep over the
# zoo's head layouts, PERF.md section 5): from 512 where a kv head serves a
# group of query heads; from 1024 where it serves one (a grid step then
# holds one head's work for its K and V tiles).
FUSED_ATTENTION_MIN_SEQ = 512
FUSED_ATTENTION_MIN_SEQ_MHA = 1024


def fused_attention_fits(q: jnp.ndarray, k: jnp.ndarray,
                         causal: bool) -> bool:
    """Self-attention over q: (B, S, H, D), k: (B, S, Kv, D) takes the
    fused Pallas kernel (``kernels.ops.flash_attention``) on a TPU lowering:
    causal, over the whole sequence or a sliding window (the kernel skips
    the tiles outside it), S at least the threshold above for its group,
    operands held whole by one device (``one_device``), and a shape the
    kernel's tiles fit (``kernels.flash_attention.fits``)."""
    s, group = q.shape[1], q.shape[2] // k.shape[2]
    min_seq = (FUSED_ATTENTION_MIN_SEQ_MHA if group == 1
               else FUSED_ATTENTION_MIN_SEQ)
    if not (causal and s >= min_seq and one_device()):
        return False
    # imported only here: Pallas adds a second to the start of a process
    from repro.kernels.flash_attention import fits
    return fits(s, s, group, q.shape[3])


def rope_yarn(cfg: ModelConfig, window: int) -> Optional[YarnConfig]:
    """The YaRN scaling of a layer's rope: the config's for a layer over the
    whole sequence, none for a sliding-window layer."""
    return cfg.yarn if window == 0 else None


@scoped("attention")
def attention_block(p: Params, x: jnp.ndarray, cfg: ModelConfig,
                    positions: jnp.ndarray, window: int = 0,
                    use_rope: bool = True,
                    causal: bool = True) -> jnp.ndarray:
    """Self-attention over x: (B, S, d); full-sequence layers take the
    config's YaRN scaling, if any (``rope_yarn``)."""
    q, k, v = _qkv(p, x, x)
    if use_rope:
        yarn = rope_yarn(cfg, window)
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
    q, k, v = _shard_q(q), _shard_kv(k), _shard_kv(v)

    def unfused(q, k, v):
        if (cfg.attention_impl == "chunked" and causal
                and x.shape[1] > cfg.attention_chunk):
            return chunked_attention(q, k, v, cfg, causal=True, window=window)
        mask = (causal_mask(x.shape[1], x.shape[1], window=window)
                if causal else None)
        return mha_logits_to_out(q, k, v, mask, cfg)

    if fused_attention_fits(q, k, causal):
        from repro.kernels.ops import flash_attention
        out = jax.lax.platform_dependent(
            q, k, v,
            tpu=lambda q, k, v: flash_attention(q, k, v, window=window,
                                                interpret=False),
            default=unfused)
    else:
        out = unfused(q, k, v)
    out = shard(out, "act_heads")
    return jnp.einsum("...shk,hkd->...sd", out, p["wo"].astype(x.dtype))


@scoped("attention")
def cross_attention_block(p: Params, x: jnp.ndarray, enc: jnp.ndarray,
                          cfg: ModelConfig, gated: bool = True) -> jnp.ndarray:
    """Cross-attention: queries from x (B,S,d), keys/values from enc (B,T,d)."""
    q, k, v = _qkv(p, x, enc)
    q, k, v = _shard_q(q), _shard_kv(k), _shard_kv(v)
    out = mha_logits_to_out(q, k, v, None, cfg)
    y = jnp.einsum("...shk,hkd->...sd", out, p["wo"].astype(x.dtype))
    if gated and "gate" in p:
        y = jnp.tanh(p["gate"]).astype(x.dtype) * y
    return y


# -- decode-path attention with a KV cache -----------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  n_slots: int, window: int = 0) -> Params:
    """One stacked cache for ``n_slots`` attention layers.

    Sliding-window layers keep a rolled buffer of ``window`` positions.
    Layout (n_slots, B, S, n_kv, head_dim): batch shards over data, cache
    sequence over model (flash-decoding style partial-softmax combine is
    delegated to the SPMD partitioner).
    """
    s = min(max_len, window) if window > 0 else max_len
    shape = (n_slots, batch, s, cfg.n_kv, cfg.head_dim)
    dt = jnp.dtype(cfg.dtype)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


@scoped("attention")
def decode_attention(p: Params, x: jnp.ndarray, cache_k: jnp.ndarray,
                     cache_v: jnp.ndarray, pos: jnp.ndarray,
                     cfg: ModelConfig, window: int = 0,
                     use_rope: bool = True):
    """One-token decode. x: (B, 1, d); cache_*: (B, S, n_kv, hd);
    pos: scalar int32 (current absolute position). Returns (out, k, v)."""
    q, k, v = _qkv(p, x, x)
    if use_rope:
        ppos = jnp.full((x.shape[0], 1), pos, jnp.int32)
        yarn = rope_yarn(cfg, window)
        q = apply_rope(q, ppos, cfg.rope_theta, yarn)
        k = apply_rope(k, ppos, cfg.rope_theta, yarn)
    s_cache = cache_k.shape[1]
    slot = jnp.where(window > 0, pos % jnp.maximum(s_cache, 1), pos)
    ck = jax.lax.dynamic_update_slice(cache_k, k.astype(cache_k.dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cache_v, v.astype(cache_v.dtype),
                                      (0, slot, 0, 0))
    ck, cv = shard(ck, "kv_cache"), shard(cv, "kv_cache")
    idx = jnp.arange(s_cache)
    if window > 0:
        # ring buffer: slot i holds absolute position pos - ((slot - i) mod S);
        # valid iff that position exists (age < min(pos+1, S)).
        age = (slot - idx) % s_cache
        valid = age < jnp.minimum(pos + 1, s_cache)
    else:
        valid = idx <= pos
    mask = valid[None, None, None, :]
    out = mha_logits_to_out(q, ck.astype(q.dtype), cv.astype(q.dtype),
                            mask, cfg)
    y = jnp.einsum("...shk,hkd->...sd", out, p["wo"].astype(x.dtype))
    return y, ck, cv
