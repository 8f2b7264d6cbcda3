"""Recurrent sequence-mixing blocks: RG-LRU (Griffin/RecurrentGemma),
sLSTM and mLSTM (xLSTM).

Each block kind provides:
  init_*(key, cfg)                          -> params
  apply_*(params, x, cfg)                   -> y           (train, full seq)
  step_*(params, x1, state, cfg)            -> (y1, state) (decode, 1 token)
  init_*_state(cfg, batch)                  -> state

Train-time RG-LRU uses ``jax.lax.associative_scan`` (parallel prefix —
TPU-friendly; the Pallas kernel in ``repro.kernels.rglru_scan`` implements
the same recurrence with chunked VMEM tiling). sLSTM/mLSTM use the
stabilized exponential-gating recurrences of the xLSTM paper via
``lax.scan`` over time.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard
from .config import ModelConfig
from .layers import Params, dense_init, scoped

_RGLRU_C = 8.0


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (Griffin): conv1d + gated linear recurrence
# ---------------------------------------------------------------------------


def init_rglru(key, cfg: ModelConfig) -> Params:
    d, r = cfg.d_model, cfg.rnn_width
    ks = jax.random.split(key, 7)
    # Λ init so that a = sigmoid(lam)^c spreads over [0.9, 0.999]
    u = jax.random.uniform(ks[5], (r,), minval=0.9, maxval=0.999)
    lam = jnp.log(jnp.exp(-jnp.log(u) / _RGLRU_C) - 1.0)  # softplus^-1
    return {
        "rg_in": {"wx": dense_init(ks[0], (d, r)),      # recurrence branch
                  "wy": dense_init(ks[1], (d, r))},     # gate branch
        "rg_gates": {"wa": dense_init(ks[2], (r, r)),   # recurrence gate
                     "wi": dense_init(ks[3], (r, r))},  # input gate
        "rg_lambda": lam,
        "conv": jax.random.normal(ks[4], (cfg.conv_width, r),
                                  dtype=jnp.float32) * 0.1,
        "rg_out": {"wo": dense_init(ks[6], (r, d))},
    }


def _rglru_coeffs(p: Params, u: jnp.ndarray):
    """u: (..., r) pre-activation inputs -> (a, b) recurrence coefficients."""
    dt32 = jnp.float32
    rgate = jax.nn.sigmoid(
        jnp.einsum("...r,rk->...k", u, p["rg_gates"]["wa"]).astype(dt32))
    igate = jax.nn.sigmoid(
        jnp.einsum("...r,rk->...k", u, p["rg_gates"]["wi"]).astype(dt32))
    log_a = -_RGLRU_C * jax.nn.softplus(p["rg_lambda"]).astype(dt32) * rgate
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * igate * u.astype(dt32)
    return a, b


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                 state: jnp.ndarray = None) -> jnp.ndarray:
    """Depthwise causal conv. x: (B,S,R), w: (W,R). state: (B,W-1,R)|None."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros(x.shape[:-2] + (width - 1, x.shape[-1]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=-2)
    out = sum(xp[..., i:i + x.shape[-2], :] * w[i].astype(x.dtype)
              for i in range(width))
    return out


@scoped("recurrent")
def apply_rglru(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """x: (B, S, d) -> (B, S, d). Zero initial state."""
    dt = x.dtype
    u = jnp.einsum("...d,dr->...r", x, p["rg_in"]["wx"].astype(dt))
    gate = jax.nn.gelu(
        jnp.einsum("...d,dr->...r", x, p["rg_in"]["wy"].astype(dt)),
        approximate=True)
    u = _causal_conv(u, p["conv"])
    u = shard(u, "act_rnn")
    a, b = _rglru_coeffs(p, u)

    if cfg.use_flash_kernel and x.shape[1] >= 256:
        from repro.kernels.ops import rglru_scan
        h = rglru_scan(a, b)
    else:
        def comb(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, a2 * b1 + b2
        _, h = jax.lax.associative_scan(comb, (a, b), axis=-2)
    h = h.astype(dt) * gate
    h = shard(h, "act_rnn")
    return jnp.einsum("...r,rd->...d", h, p["rg_out"]["wo"].astype(dt))


def init_rglru_state(cfg: ModelConfig, batch: int) -> Params:
    r = cfg.rnn_width
    return {"h": jnp.zeros((batch, r), jnp.float32),
            "conv": jnp.zeros((batch, cfg.conv_width - 1, r), jnp.float32)}


def step_rglru(p: Params, x: jnp.ndarray, state: Params,
               cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    """x: (B, 1, d); state: {h: (B,R), conv: (B,W-1,R)}."""
    dt = x.dtype
    u = jnp.einsum("...d,dr->...r", x, p["rg_in"]["wx"].astype(dt))
    gate = jax.nn.gelu(
        jnp.einsum("...d,dr->...r", x, p["rg_in"]["wy"].astype(dt)),
        approximate=True)
    u_seq = _causal_conv(u, p["conv"], state=state["conv"])
    new_conv = jnp.concatenate(
        [state["conv"][:, 1:], u.astype(jnp.float32)], axis=1)
    a, b = _rglru_coeffs(p, u_seq)
    h = a[:, 0] * state["h"] + b[:, 0]                    # (B, R)
    y = h[:, None].astype(dt) * gate
    out = jnp.einsum("...r,rd->...d", y, p["rg_out"]["wo"].astype(dt))
    return out, {"h": h, "conv": new_conv}


# ---------------------------------------------------------------------------
# sLSTM (xLSTM): scalar memory, exponential gating, head-wise recurrence
# ---------------------------------------------------------------------------


def init_slstm(key, cfg: ModelConfig) -> Params:
    d, nh = cfg.d_model, cfg.n_heads
    hd = d // nh
    ks = jax.random.split(key, 3)
    # 4 gates (i, f, z, o) from input; recurrent head-wise weights
    return {
        "lstm_wx": dense_init(ks[0], (d, 4, nh, hd), in_axis=0),
        "lstm_wh": dense_init(ks[1], (nh, hd, 4, hd), in_axis=1) * 0.5,
        "lstm_b": jnp.zeros((4, nh, hd), jnp.float32),
        "rg_out": {"wo": dense_init(ks[2], (d, d))},
    }


def _slstm_cell(gx, h_prev, c_prev, n_prev, m_prev, wh):
    """One sLSTM time step (stabilized exponential gating).

    gx: (B, 4, nh, hd) input contribution; states: (B, nh, hd)."""
    gr = jnp.einsum("bhk,hkgl->bghl", h_prev, wh)   # recurrent contribution
    g = (gx + gr).astype(jnp.float32)
    i_t, f_t, z_t, o_t = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
    m_t = jnp.maximum(f_t + m_prev, i_t)
    i_p = jnp.exp(i_t - m_t)
    f_p = jnp.exp(f_t + m_prev - m_t)
    c_t = f_p * c_prev + i_p * jnp.tanh(z_t)
    n_t = f_p * n_prev + i_p
    h_t = jax.nn.sigmoid(o_t) * c_t / jnp.maximum(n_t, 1.0)
    return h_t, c_t, n_t, m_t


def _chunked_time_scan(scan_fn, carry0, xs_t, seq_len: int,
                       time_chunk: int):
    """scan over time with per-chunk rematerialization: saves only chunk
    boundary carries for the backward pass (memory ~ S/time_chunk)."""
    if not time_chunk or seq_len % time_chunk or seq_len <= time_chunk:
        return jax.lax.scan(scan_fn, carry0, xs_t)
    n_chunks = seq_len // time_chunk

    def chunk_fn(carry, xs_chunk):
        return jax.lax.scan(scan_fn, carry, xs_chunk)

    chunk_fn = jax.checkpoint(
        chunk_fn, policy=jax.checkpoint_policies.nothing_saveable)
    xs_chunked = jax.tree_util.tree_map(
        lambda a: a.reshape((n_chunks, time_chunk) + a.shape[1:]), xs_t)
    carry, ys = jax.lax.scan(chunk_fn, carry0, xs_chunked)
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((seq_len,) + a.shape[2:]), ys)
    return carry, ys


@scoped("recurrent")
def apply_slstm(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    b, s, d = x.shape
    nh = cfg.n_heads
    hd = d // nh
    dt = x.dtype
    gx = jnp.einsum("bsd,dghl->bsghl", x, p["lstm_wx"].astype(dt))
    gx = gx.astype(jnp.float32) + p["lstm_b"]
    zeros = jnp.zeros((b, nh, hd), jnp.float32)
    m0 = jnp.full((b, nh, hd), -1e30, jnp.float32)

    def scan_fn(carry, gx_t):
        h, c, n, m = carry
        h, c, n, m = _slstm_cell(gx_t, h, c, n, m, p["lstm_wh"])
        return (h, c, n, m), h

    _, hs = _chunked_time_scan(scan_fn, (zeros, zeros, zeros, m0),
                               jnp.swapaxes(gx, 0, 1), s, cfg.time_chunk)
    hs = jnp.swapaxes(hs, 0, 1).reshape(b, s, d).astype(dt)
    return jnp.einsum("...d,dk->...k", hs, p["rg_out"]["wo"].astype(dt))


def init_slstm_state(cfg: ModelConfig, batch: int) -> Params:
    nh = cfg.n_heads
    hd = cfg.d_model // nh
    z = jnp.zeros((batch, nh, hd), jnp.float32)
    return {"h": z, "c": z, "n": z,
            "m": jnp.full((batch, nh, hd), -1e30, jnp.float32)}


def step_slstm(p: Params, x: jnp.ndarray, state: Params,
               cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    b = x.shape[0]
    dt = x.dtype
    gx = jnp.einsum("bsd,dghl->bsghl", x, p["lstm_wx"].astype(dt))
    gx = gx[:, 0].astype(jnp.float32) + p["lstm_b"]
    h, c, n, m = _slstm_cell(gx, state["h"], state["c"], state["n"],
                             state["m"], p["lstm_wh"])
    y = h.reshape(b, 1, -1).astype(dt)
    out = jnp.einsum("...d,dk->...k", y, p["rg_out"]["wo"].astype(dt))
    return out, {"h": h, "c": c, "n": n, "m": m}


# ---------------------------------------------------------------------------
# mLSTM (xLSTM): matrix memory C (hd x hd per head), covariance update
# ---------------------------------------------------------------------------


def init_mlstm(key, cfg: ModelConfig) -> Params:
    d, nh = cfg.d_model, cfg.n_heads
    hd = d // nh
    ks = jax.random.split(key, 4)
    return {
        "lstm_wqkv": dense_init(ks[0], (d, 3, nh, hd), in_axis=0),
        "lstm_wif": dense_init(ks[1], (d, 2, nh), in_axis=0),
        "lstm_bif": jnp.stack([jnp.zeros((nh,)), jnp.full((nh,), 3.0)]),
        "lstm_wog": dense_init(ks[2], (d, d)),
        "rg_out": {"wo": dense_init(ks[3], (d, d))},
    }


def _mlstm_gates(p: Params, x: jnp.ndarray):
    dt = x.dtype
    qkv = jnp.einsum("bsd,dghl->bsghl", x, p["lstm_wqkv"].astype(dt))
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B,S,nh,hd)
    iflog = jnp.einsum("bsd,dgh->bsgh", x, p["lstm_wif"].astype(dt))
    iflog = iflog.astype(jnp.float32) + p["lstm_bif"]
    i_t, f_t = iflog[:, :, 0], iflog[:, :, 1]           # (B,S,nh)
    f_t = -jax.nn.softplus(-f_t)                        # logsigmoid
    og = jax.nn.sigmoid(
        jnp.einsum("bsd,dk->bsk", x, p["lstm_wog"].astype(dt)))
    hd = q.shape[-1]
    k = k / math.sqrt(hd)
    return q, k, v, i_t, f_t, og


@scoped("recurrent")
def apply_mlstm(p: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    b, s, d = x.shape
    nh = cfg.n_heads
    hd = d // nh
    dt = x.dtype
    q, k, v, i_t, f_t, og = _mlstm_gates(p, x)

    def scan_fn(carry, inp):
        C, n, m = carry                                  # (B,nh,hd,hd) ...
        qt, kt, vt, it, ft = inp
        m_t = jnp.maximum(ft + m, it)
        i_p = jnp.exp(it - m_t)[..., None]               # (B,nh,1)
        f_p = jnp.exp(ft + m - m_t)[..., None]
        C = f_p[..., None] * C + i_p[..., None] * \
            (vt[..., :, None] * kt[..., None, :])        # v k^T
        n = f_p * n + i_p * kt
        num = jnp.einsum("bhkl,bhl->bhk", C, qt)
        den = jnp.maximum(jnp.abs(jnp.einsum("bhl,bhl->bh", n, qt)),
                          1.0)[..., None]
        return (C, n, m_t), num / den

    C0 = jnp.zeros((b, nh, hd, hd), jnp.float32)
    n0 = jnp.zeros((b, nh, hd), jnp.float32)
    m0 = jnp.full((b, nh), -1e30, jnp.float32)
    xs = (jnp.moveaxis(q, 1, 0).astype(jnp.float32),   # (S,B,nh,hd)
          jnp.moveaxis(k, 1, 0).astype(jnp.float32),
          jnp.moveaxis(v, 1, 0).astype(jnp.float32),
          jnp.moveaxis(i_t, 1, 0), jnp.moveaxis(f_t, 1, 0))
    _, hs = _chunked_time_scan(scan_fn, (C0, n0, m0), xs, s,
                               cfg.time_chunk)
    hs = jnp.moveaxis(hs, 0, 1).reshape(b, s, d).astype(dt)   # (B,S,d)
    hs = hs * og.astype(dt)
    return jnp.einsum("...d,dk->...k", hs, p["rg_out"]["wo"].astype(dt))


def init_mlstm_state(cfg: ModelConfig, batch: int) -> Params:
    nh = cfg.n_heads
    hd = cfg.d_model // nh
    return {"C": jnp.zeros((batch, nh, hd, hd), jnp.float32),
            "n": jnp.zeros((batch, nh, hd), jnp.float32),
            "m": jnp.full((batch, nh), -1e30, jnp.float32)}


def step_mlstm(p: Params, x: jnp.ndarray, state: Params,
               cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    b, _, d = x.shape
    dt = x.dtype
    q, k, v, i_t, f_t, og = _mlstm_gates(p, x)
    qt, kt, vt = (a[:, 0].astype(jnp.float32) for a in (q, k, v))
    it, ft = i_t[:, 0], f_t[:, 0]
    C, n, m = state["C"], state["n"], state["m"]
    m_t = jnp.maximum(ft + m, it)
    i_p = jnp.exp(it - m_t)[..., None]
    f_p = jnp.exp(ft + m - m_t)[..., None]
    C = f_p[..., None] * C + i_p[..., None] * (vt[..., :, None] *
                                               kt[..., None, :])
    n = f_p * n + i_p * kt
    num = jnp.einsum("bhkl,bhl->bhk", C, qt)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhl,bhl->bh", n, qt)),
                      1.0)[..., None]
    h = (num / den).reshape(b, 1, d).astype(dt) * og.astype(dt)
    out = jnp.einsum("...d,dk->...k", h, p["rg_out"]["wo"].astype(dt))
    return out, {"C": C, "n": n, "m": m_t}
