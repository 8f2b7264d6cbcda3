"""The unified LM: init / forward / loss / decode for every assigned arch.

The layer stack is a ``lax.scan`` over repeating pattern groups (stacked
parameters; HLO size independent of depth) plus an unrolled remainder.
Each *slot* in the pattern is one block (norms + mixer + optional FFN).

Public API:
  init_params(key, cfg)                         parameter pytree
  param_shapes(cfg)                             ShapeDtypeStruct pytree
  forward(params, batch, cfg)                   (logits, aux)
  loss_fn(params, batch, cfg)                   (loss, metrics)
  init_decode_state(cfg, batch, max_len)        decode cache/state pytree
  decode_state_shapes(cfg, batch, max_len)
  serve_step(params, state, token, cfg)         (logits, new_state)
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import shard
from . import recurrent as rec
from .config import MOE_KINDS, WINDOW_KINDS, ModelConfig
from .layers import (Params, apply_mlp, apply_norm, attention_block,
                     cross_attention_block, decode_attention,
                     dense_init, embed_init, init_attention, init_mlp,
                     init_norm, mha_logits_to_out)
from .moe import apply_moe, init_moe

Batch = Dict[str, jnp.ndarray]
SELF_ATTENTION_KINDS = ("attn", "local", "moe", "local_moe", "encdec")


# ---------------------------------------------------------------------------
# Per-slot block init
# ---------------------------------------------------------------------------


def _init_block(key, kind: str, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": init_norm(cfg)}
    if kind in SELF_ATTENTION_KINDS:
        p["attn"] = init_attention(ks[0], cfg)
    if kind == "encdec":
        p["norm_x"] = init_norm(cfg)
        p["xattn"] = init_attention(ks[1], cfg, cross=False)
    if kind == "xattn":
        p["xattn"] = init_attention(ks[1], cfg, cross=True)
    if kind == "rglru":
        p["rglru"] = rec.init_rglru(ks[2], cfg)
    if kind == "slstm":
        p["slstm"] = rec.init_slstm(ks[2], cfg)
    if kind == "mlstm":
        p["mlstm"] = rec.init_mlstm(ks[2], cfg)
    if kind in MOE_KINDS:
        p["norm2"] = init_norm(cfg)
        p["moe"] = init_moe(ks[3], cfg)
        if cfg.dense_residual_ff:
            p["dense_ff"] = init_mlp(ks[4], cfg, d_ff=cfg.dense_residual_ff)
    elif kind in ("attn", "local", "xattn", "encdec", "rglru") and cfg.d_ff:
        p["norm2"] = init_norm(cfg)
        p["mlp"] = init_mlp(ks[5], cfg)
    return p


def _zero_aux(cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    """The losses MoE layers add, and with experts the assignments each
    held expert received, summed over the layers."""
    aux = {"aux_loss": jnp.zeros((), jnp.float32),
           "z_loss": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None:
        aux["expert_counts"] = jnp.zeros((cfg.moe.n_held,), jnp.int32)
    return aux


def _apply_ffn(kind: str, p: Params, x: jnp.ndarray, cfg: ModelConfig,
               aux: Dict) -> Tuple[jnp.ndarray, Dict]:
    """The block's feed-forward half, added to the residual: the MoE FFN
    (its aux losses and counts replace ``aux``), or the MLP."""
    if kind in MOE_KINDS:
        h = apply_norm(p["norm2"], x, cfg)
        moe_out, aux = apply_moe(p["moe"], h, cfg)
        if "dense_ff" in p:
            moe_out = moe_out + apply_mlp(p["dense_ff"], h, cfg)
        x = x + moe_out
    elif "mlp" in p:
        x = x + apply_mlp(p["mlp"], apply_norm(p["norm2"], x, cfg), cfg)
    return x, aux


def _apply_block(kind: str, p: Params, x: jnp.ndarray, cfg: ModelConfig,
                 positions: jnp.ndarray,
                 enc: Optional[jnp.ndarray]) -> Tuple[jnp.ndarray, Dict]:
    aux = _zero_aux(cfg)
    if kind in SELF_ATTENTION_KINDS:
        w = cfg.window if kind in WINDOW_KINDS else 0
        x = x + attention_block(p["attn"], apply_norm(p["norm1"], x, cfg),
                                cfg, positions, window=w,
                                use_rope=(cfg.rope_theta > 0))
    if kind == "encdec":
        x = x + cross_attention_block(
            p["xattn"], apply_norm(p["norm_x"], x, cfg), enc, cfg,
            gated=False)
    if kind == "xattn":
        x = x + cross_attention_block(
            p["xattn"], apply_norm(p["norm1"], x, cfg), enc, cfg, gated=True)
    if kind == "rglru":
        x = x + rec.apply_rglru(p["rglru"], apply_norm(p["norm1"], x, cfg),
                                cfg)
    if kind == "slstm":
        x = x + rec.apply_slstm(p["slstm"], apply_norm(p["norm1"], x, cfg),
                                cfg)
    if kind == "mlstm":
        x = x + rec.apply_mlstm(p["mlstm"], apply_norm(p["norm1"], x, cfg),
                                cfg)
    x, aux = _apply_ffn(kind, p, x, cfg, aux)
    x = shard(x, "act_seq" if cfg.seq_parallel_residual else "act_btd")
    return x, aux


# ---------------------------------------------------------------------------
# Whisper-style encoder (bidirectional; stub conv frontend upstream)
# ---------------------------------------------------------------------------


def _init_encoder(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, cfg.encoder_layers + 1)
    layers = []
    for i in range(cfg.encoder_layers):
        k1, k2 = jax.random.split(ks[i])
        layers.append({"norm1": init_norm(cfg),
                       "attn": init_attention(k1, cfg),
                       "norm2": init_norm(cfg),
                       "mlp": init_mlp(k2, cfg)})
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {"layers": stacked, "final_norm": init_norm(cfg),
            "pos": embed_init(ks[-1], (cfg.encoder_len, cfg.d_model)) * 0.02}


def _run_encoder(p: Params, frames: jnp.ndarray,
                 cfg: ModelConfig) -> jnp.ndarray:
    """frames: (B, T, d) stub conv-frontend output; bidirectional attention."""
    x = frames + p["pos"][None, : frames.shape[1]].astype(frames.dtype)
    positions = jnp.arange(frames.shape[1])[None, :]

    def body(x, lp):
        x = x + attention_block(lp["attn"], apply_norm(lp["norm1"], x, cfg),
                                cfg, positions, use_rope=False, causal=False)
        x = x + apply_mlp(lp["mlp"], apply_norm(lp["norm2"], x, cfg), cfg)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, p["layers"])
    return apply_norm(p["final_norm"], x, cfg)


# ---------------------------------------------------------------------------
# Full-model init
# ---------------------------------------------------------------------------


def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 6 + cfg.n_layers)
    p: Params = {"embed": embed_init(ks[0],
                                     (cfg.padded_vocab, cfg.d_model)) * 0.02,
                 "final_norm": init_norm(cfg)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (cfg.d_model, cfg.padded_vocab))
    if cfg.encoder_layers:
        p["encoder"] = _init_encoder(ks[2], cfg)
        # learned decoder positions sized for the largest assigned shape
        p["pos_embed"] = embed_init(ks[3], (32_768, cfg.d_model)) * 0.02

    if cfg.n_groups > 0:
        groups = []
        for gi in range(cfg.n_groups):
            slots = {}
            for si, kind in enumerate(cfg.pattern):
                slots[f"s{si}_{kind}"] = _init_block(
                    ks[6 + gi * len(cfg.pattern) + si] if
                    6 + gi * len(cfg.pattern) + si < len(ks) else
                    jax.random.fold_in(ks[4], gi * 131 + si), kind, cfg)
            groups.append(slots)
        p["scan"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *groups)
    if cfg.n_tail:
        p["tail"] = {
            f"t{si}_{kind}": _init_block(jax.random.fold_in(ks[5], si),
                                         kind, cfg)
            for si, kind in enumerate(cfg.tail_pattern)}
    return p


def param_shapes(cfg: ModelConfig):
    """Parameter ShapeDtypeStructs without allocating (for the dry-run)."""
    return jax.eval_shape(lambda k: init_params(k, cfg),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def param_count(cfg: ModelConfig) -> int:
    shapes = param_shapes(cfg)
    return sum(int(math.prod(l.shape))
               for l in jax.tree_util.tree_leaves(shapes))


def active_param_count(cfg: ModelConfig) -> int:
    """Parameters touched per token (MoE: shared + the routed experts a
    token takes among those held, top_k * held / num_experts of them on
    average)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    per_expert = 3 * cfg.d_model * cfg.d_expert_eff
    n_moe_layers = (cfg.n_groups * sum(k in MOE_KINDS for k in cfg.pattern)
                    + sum(k in MOE_KINDS for k in cfg.tail_pattern))
    inactive = (n_moe_layers * per_expert * m.n_held
                * (m.num_experts - m.top_k) // m.num_experts)
    return total - inactive


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------


def _get_encoder_states(params: Params, batch: Batch,
                        cfg: ModelConfig) -> Optional[jnp.ndarray]:
    if cfg.encoder_layers:
        return _run_encoder(params["encoder"], batch["frames"], cfg)
    if cfg.cross_len and "enc_embed" in batch:
        return batch["enc_embed"]
    return None


def forward(params: Params, batch: Batch,
            cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    tokens = batch["tokens"]
    b, s = tokens.shape
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        x = x * math.sqrt(cfg.d_model)
    if cfg.encoder_layers:
        x = x + params["pos_embed"][None, :s].astype(dt)
    x = shard(x, "act_btd")
    positions = jnp.arange(s)[None, :]
    enc = _get_encoder_states(params, batch, cfg)
    if enc is not None:
        enc = enc.astype(dt)

    aux_total = _zero_aux(cfg)

    def block(kind, p, x):
        return _apply_block(kind, p, x, cfg, positions, enc)

    if cfg.remat:
        # each block rematerialised on its own, so that the backward pass
        # holds one block's intermediates at a time, not a whole group's
        block = jax.checkpoint(block, static_argnums=(0,),
                               policy=jax.checkpoint_policies.nothing_saveable)

    def group_body(carry, gp):
        x, aux = carry
        for si, kind in enumerate(cfg.pattern):
            x, a = block(kind, gp[f"s{si}_{kind}"], x)
            aux = jax.tree_util.tree_map(jnp.add, aux, a)
        return (x, aux), None

    if cfg.n_groups > 0:
        (x, aux_total), _ = jax.lax.scan(group_body, (x, aux_total),
                                         params["scan"])
    for si, kind in enumerate(cfg.tail_pattern):
        x, a = _apply_block(kind, params["tail"][f"t{si}_{kind}"], x, cfg,
                            positions, enc)
        aux_total = jax.tree_util.tree_map(jnp.add, aux_total, a)

    x = apply_norm(params["final_norm"], x, cfg)
    with jax.named_scope("lm_head"):
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))
        if cfg.logits_softcap > 0:
            logits = cfg.logits_softcap * jnp.tanh(
                logits.astype(jnp.float32) / cfg.logits_softcap).astype(dt)
        logits = _mask_pad_vocab(logits, cfg)
    logits = shard(logits, "logits")
    return logits, aux_total


def _mask_pad_vocab(logits: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    if cfg.padded_vocab == cfg.vocab:
        return logits
    valid = jnp.arange(cfg.padded_vocab) < cfg.vocab
    neg = jnp.asarray(jnp.finfo(jnp.float32).min / 2, logits.dtype)
    return jnp.where(valid, logits, neg)


def loss_fn(params: Params, batch: Batch,
            cfg: ModelConfig) -> Tuple[jnp.ndarray, Dict]:
    logits, aux = forward(params, batch, cfg)
    labels = batch["labels"]
    with jax.named_scope("lm_head"):
        logits = logits.astype(jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(logits, labels[..., None],
                                          axis=-1)[..., 0]
        mask = batch.get("mask", jnp.ones_like(labels, jnp.float32))
        ce = jnp.sum((logz - label_logit) * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)
    loss = ce + aux["aux_loss"] + aux["z_loss"]
    return loss, {"ce": ce, **aux}


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def _slot_state(kind: str, cfg: ModelConfig, batch: int,
                max_len: int) -> Params:
    dt = jnp.dtype(cfg.dtype)
    s = min(max_len, cfg.window) if kind in WINDOW_KINDS else max_len
    st: Params = {}
    if kind in SELF_ATTENTION_KINDS:
        st["k"] = jnp.zeros((batch, s, cfg.n_kv, cfg.head_dim), dt)
        st["v"] = jnp.zeros((batch, s, cfg.n_kv, cfg.head_dim), dt)
    if kind in ("xattn", "encdec"):
        t = cfg.cross_len or cfg.encoder_len
        st["xk"] = jnp.zeros((batch, t, cfg.n_kv, cfg.head_dim), dt)
        st["xv"] = jnp.zeros((batch, t, cfg.n_kv, cfg.head_dim), dt)
    if kind == "rglru":
        st.update(rec.init_rglru_state(cfg, batch))
    if kind == "slstm":
        st.update(rec.init_slstm_state(cfg, batch))
    if kind == "mlstm":
        st.update(rec.init_mlstm_state(cfg, batch))
    return st


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    state: Params = {"pos": jnp.zeros((), jnp.int32)}
    if cfg.n_groups > 0:
        slots = {}
        for si, kind in enumerate(cfg.pattern):
            per = _slot_state(kind, cfg, batch, max_len)
            slots[f"s{si}_{kind}"] = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(
                    x[None], (cfg.n_groups,) + x.shape).copy(), per)
        state["scan"] = slots
    if cfg.n_tail:
        state["tail"] = {
            f"t{si}_{kind}": _slot_state(kind, cfg, batch, max_len)
            for si, kind in enumerate(cfg.tail_pattern)}
    return state


def decode_state_shapes(cfg: ModelConfig, batch: int, max_len: int):
    return jax.eval_shape(lambda: init_decode_state(cfg, batch, max_len))


def precompute_cross_kv(params: Params, state: Params, enc: jnp.ndarray,
                        cfg: ModelConfig) -> Params:
    """Fill the xk/xv entries of a decode state from encoder states."""

    def fill(slot_params, slot_state, stacked: bool):
        if "xk" not in slot_state:
            return slot_state
        ap = slot_params["xattn"]

        def one(wk, wv):
            k = jnp.einsum("btd,dhk->bthk", enc, wk.astype(enc.dtype))
            v = jnp.einsum("btd,dhk->bthk", enc, wv.astype(enc.dtype))
            return k, v

        if stacked:
            k, v = jax.vmap(one)(ap["wk"], ap["wv"])
        else:
            k, v = one(ap["wk"], ap["wv"])
        out = dict(slot_state)
        out["xk"], out["xv"] = k.astype(slot_state["xk"].dtype), \
            v.astype(slot_state["xv"].dtype)
        return out

    state = dict(state)
    if "scan" in state:
        state["scan"] = {
            key: fill(params["scan"][key], st, True)
            for key, st in state["scan"].items()}
    if "tail" in state:
        state["tail"] = {
            key: fill(params["tail"][key], st, False)
            for key, st in state["tail"].items()}
    return state


def _step_block(kind: str, p: Params, x: jnp.ndarray, st: Params,
                pos: jnp.ndarray, cfg: ModelConfig) -> Tuple[jnp.ndarray,
                                                             Params]:
    new_st = dict(st)
    if kind in SELF_ATTENTION_KINDS:
        w = cfg.window if kind in WINDOW_KINDS else 0
        h = apply_norm(p["norm1"], x, cfg)
        y, ck, cv = decode_attention(p["attn"], h, st["k"], st["v"], pos,
                                     cfg, window=w,
                                     use_rope=(cfg.rope_theta > 0))
        new_st["k"], new_st["v"] = ck, cv
        x = x + y
    if kind == "encdec":
        h = apply_norm(p["norm_x"], x, cfg)
        q = jnp.einsum("...sd,dhk->...shk", h, p["xattn"]["wq"].astype(x.dtype))
        o = mha_logits_to_out(q, st["xk"].astype(x.dtype),
                              st["xv"].astype(x.dtype), None, cfg)
        x = x + jnp.einsum("...shk,hkd->...sd", o,
                           p["xattn"]["wo"].astype(x.dtype))
    if kind == "xattn":
        h = apply_norm(p["norm1"], x, cfg)
        q = jnp.einsum("...sd,dhk->...shk", h, p["xattn"]["wq"].astype(x.dtype))
        o = mha_logits_to_out(q, st["xk"].astype(x.dtype),
                              st["xv"].astype(x.dtype), None, cfg)
        y = jnp.einsum("...shk,hkd->...sd", o,
                       p["xattn"]["wo"].astype(x.dtype))
        if "gate" in p["xattn"]:
            y = jnp.tanh(p["xattn"]["gate"]).astype(x.dtype) * y
        x = x + y
    if kind == "rglru":
        y, s2 = rec.step_rglru(p["rglru"], apply_norm(p["norm1"], x, cfg),
                               {"h": st["h"], "conv": st["conv"]}, cfg)
        new_st.update(s2)
        x = x + y
    if kind == "slstm":
        y, s2 = rec.step_slstm(p["slstm"], apply_norm(p["norm1"], x, cfg),
                               {k: st[k] for k in ("h", "c", "n", "m")}, cfg)
        new_st.update(s2)
        x = x + y
    if kind == "mlstm":
        y, s2 = rec.step_mlstm(p["mlstm"], apply_norm(p["norm1"], x, cfg),
                               {k: st[k] for k in ("C", "n", "m")}, cfg)
        new_st.update(s2)
        x = x + y
    x, _ = _apply_ffn(kind, p, x, cfg, {})
    return x, new_st


def serve_step(params: Params, state: Params, token: jnp.ndarray,
               cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    """One decode step. token: (B,) int32. Returns (logits (B, V), state)."""
    dt = jnp.dtype(cfg.dtype)
    pos = state["pos"]
    x = jnp.take(params["embed"], token[:, None], axis=0).astype(dt)
    x = x * math.sqrt(cfg.d_model)
    if cfg.encoder_layers:
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], pos, 1, axis=0)[None].astype(dt)

    new_state: Params = {"pos": pos + 1}
    if cfg.n_groups > 0:
        def body(x, inp):
            gp, gst = inp
            out_st = {}
            for si, kind in enumerate(cfg.pattern):
                key = f"s{si}_{kind}"
                x, st2 = _step_block(kind, gp[key], x, gst[key], pos, cfg)
                out_st[key] = st2
            return x, out_st

        x, scan_st = jax.lax.scan(body, x,
                                  (params["scan"], state["scan"]))
        new_state["scan"] = scan_st
    if cfg.n_tail:
        tail_st = {}
        for si, kind in enumerate(cfg.tail_pattern):
            key = f"t{si}_{kind}"
            x, st2 = _step_block(kind, params["tail"][key], x,
                                 state["tail"][key], pos, cfg)
            tail_st[key] = st2
        new_state["tail"] = tail_st

    x = apply_norm(params["final_norm"], x, cfg)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(dt))[:, 0]
    if cfg.logits_softcap > 0:
        logits = cfg.logits_softcap * jnp.tanh(
            logits.astype(jnp.float32) / cfg.logits_softcap).astype(dt)
    logits = _mask_pad_vocab(logits, cfg)
    return logits, new_state
