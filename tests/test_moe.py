"""The dropless expert layer over a held block of experts, YaRN rope, and
Mellum2-12B (sliding + full attention, sparse experts) against a plain
float32 reference at a small size."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import (forward, init_decode_state, init_params, loss_fn,
                          serve_step)
from repro.models.config import MoEConfig, YarnConfig
from repro.models.layers import apply_rope, yarn_freqs
from repro.models.moe import apply_moe, init_moe

HIGHEST = jax.lax.Precision.HIGHEST


def mm(eq, *operands):
    return jnp.einsum(eq, *operands, precision=HIGHEST)


# ---------------------------------------------------------------------------
# Plain float32 reference of the model's blocks
# ---------------------------------------------------------------------------


def ref_norm(p, x):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * p["scale"]


def ref_rope(x, theta, yarn):
    """(B, S, H, D), pairs (i, i + D/2); YaRN frequencies and magnitude
    from the HF rope utilities' formulas, written out here."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2) / d)
    scale = 1.0
    if yarn is not None:
        def cd(r):
            return d * math.log(yarn.original_max_position
                                / (2 * math.pi * r)) / (2 * math.log(theta))
        low = max(math.floor(cd(yarn.beta_fast)), 0)
        high = min(math.ceil(cd(yarn.beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
        inv = inv / yarn.factor * ramp + inv * (1 - ramp)
        scale = yarn.attention_factor
    ang = np.arange(s)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang) * scale, jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang) * scale, jnp.float32)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def ref_attention(p, x, cfg, window):
    b, s, _ = x.shape
    yarn = cfg.yarn if window == 0 else None
    q = ref_rope(mm("bsd,dhk->bshk", x, p["wq"]), cfg.rope_theta, yarn)
    k = ref_rope(mm("bsd,dhk->bshk", x, p["wk"]), cfg.rope_theta, yarn)
    v = mm("bsd,dhk->bshk", x, p["wv"])
    g = cfg.n_heads // cfg.n_kv
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    sc = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(cfg.head_dim)
    qi, ki = np.arange(s)[:, None], np.arange(s)[None, :]
    live = ki <= qi
    if window:
        live &= ki > qi - window
    w = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), -1)
    return mm("bhqk,bkhd->bqhd", w, v).reshape(b, s, -1) @ \
        p["wo"].reshape(-1, cfg.d_model)


def ref_moe(p, x, moe: MoEConfig):
    """Every held expert on every token, weighted by the token's gate for
    it (zero unless the expert is among its top k); losses over all the
    router's experts."""
    t = x.reshape(-1, x.shape[-1])
    logits = mm("td,de->te", t, p["router"]["w"])
    probs = jax.nn.softmax(logits, -1)
    gate, idx = jax.lax.top_k(probs, moe.top_k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)
    dense = jnp.zeros_like(probs).at[
        jnp.arange(t.shape[0])[:, None], idx].set(gate)
    held = dense[:, moe.first_held:moe.first_held + moe.n_held]
    w = p["experts"]
    h = jax.nn.silu(mm("td,edf->tef", t, w["wg"])) * \
        mm("td,edf->tef", t, w["wi"])
    out = mm("tef,efd,te->td", h, w["wo"], held)
    counts = jnp.sum(jax.nn.one_hot(idx, moe.num_experts), axis=(0, 1))
    aux = moe.aux_coef * moe.num_experts * jnp.sum(
        jnp.mean(probs, 0) * counts / t.shape[0])
    z = moe.router_z_coef * jnp.mean(
        jax.scipy.special.logsumexp(logits, -1) ** 2)
    return out.reshape(x.shape), aux + z


def ref_loss(params, tokens, labels, cfg):
    """(mean cross-entropy + the MoE losses, logits over the vocabulary)."""
    x = params["embed"][tokens] * math.sqrt(cfg.d_model)
    extra = 0.0
    layers = [(kind, jax.tree_util.tree_map(
        lambda a, g=g: a[g], params["scan"][f"s{i}_{kind}"]))
        for g in range(cfg.n_groups) for i, kind in enumerate(cfg.pattern)]
    for kind, p in layers:
        window = cfg.window if kind == "local_moe" else 0
        x = x + ref_attention(p["attn"], ref_norm(p["norm1"], x), cfg,
                              window)
        y, e = ref_moe(p["moe"], ref_norm(p["norm2"], x), cfg.moe)
        x, extra = x + y, extra + e
    logits = mm("bsd,dv->bsv", ref_norm(params["final_norm"], x),
                params["lm_head"])[..., : cfg.vocab]
    lse = jax.scipy.special.logsumexp(logits, -1)
    ce = jnp.mean(lse - jnp.take_along_axis(logits, labels[..., None],
                                            -1)[..., 0])
    return ce + extra, logits


def mellum(**kw):
    cfg = get_config("mellum2-12b", smoke=True)
    return cfg.replace(**{"window": 8, **kw})


def batch_of(cfg, b=2, s=32, seed=3):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                              cfg.vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ---------------------------------------------------------------------------
# Mellum2 against the reference
# ---------------------------------------------------------------------------


def test_mellum_registry_holds_the_published_model():
    cfg = get_config("mellum2-12b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim,
            cfg.vocab) == (28, 2304, 32, 4, 128, 98304)
    assert cfg.pattern == ("local_moe",) * 3 + ("moe",)
    assert (cfg.window, cfg.rope_theta) == (1024, 500_000.0)
    m = cfg.moe
    assert (m.num_experts, m.top_k, m.d_expert, m.num_shared, m.n_held) == \
        (64, 8, 896, 0, 64)
    assert cfg.yarn == YarnConfig(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert not cfg.tie_embeddings


def test_mellum_logits_loss_and_gradients_match_the_reference():
    cfg = mellum()
    assert cfg.n_groups == 2 and cfg.yarn is not None
    params = init_params(jax.random.PRNGKey(5), cfg)
    batch = batch_of(cfg)
    logits, _ = forward(params, batch, cfg)
    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch, cfg)
    (ref, ref_logits), ref_grads = jax.value_and_grad(
        lambda p: ref_loss(p, batch["tokens"], batch["labels"], cfg),
        has_aux=True)(params)
    np.testing.assert_allclose(np.asarray(logits)[..., : cfg.vocab],
                               np.asarray(ref_logits), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(float(loss), float(ref), rtol=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    want = dict(jax.tree_util.tree_flatten_with_path(ref_grads)[0])
    for path, g in flat:
        r = np.asarray(want[path])
        err = np.max(np.abs(np.asarray(g) - r)) / max(np.max(np.abs(r)),
                                                      1e-12)
        assert err < 1e-4, (jax.tree_util.keystr(path), err)
    # every assignment of every layer lands on the held experts (all 4)
    assert int(np.sum(metrics["expert_counts"])) == \
        cfg.n_layers * 2 * 32 * cfg.moe.top_k


def test_decode_through_the_window_cache_matches_forward():
    """12 tokens through a window of 4: the sliding layers' ring buffer
    wraps, the full layer's cache does not."""
    cfg = mellum(window=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    batch = batch_of(cfg, s=12)
    full, _ = forward(params, batch, cfg)
    state = init_decode_state(cfg, 2, 12)
    assert state["scan"]["s0_local_moe"]["k"].shape[2] == 4
    assert state["scan"]["s3_moe"]["k"].shape[2] == 12
    for i in range(12):
        logits, state = serve_step(params, state, batch["tokens"][:, i], cfg)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, i]),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# The expert layer: shares, drops, counts
# ---------------------------------------------------------------------------


def layer_cfg(num_experts=16, top_k=4, **moe):
    base = get_config("mellum2-12b", smoke=True)
    return base.replace(moe=MoEConfig(num_experts=num_experts, top_k=top_k,
                                      d_expert=32, **moe))


def layer_params(seed=0):
    """The uncut layer's weights: all sixteen experts."""
    return init_moe(jax.random.PRNGKey(seed), layer_cfg())


def share(p, first, held):
    return {"router": p["router"],
            "experts": jax.tree_util.tree_map(
                lambda w: w[first:first + held], p["experts"])}


def test_the_eight_shares_sum_to_the_uncut_layer():
    """Eight chips of an expert-parallel group, two experts each: their
    outputs add up to the layer that holds all sixteen, each computes the
    losses over all sixteen, and their counts are the uncut layer's."""
    cfg = layer_cfg()
    p = layer_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    whole, whole_aux = apply_moe(p, x, cfg)
    outs, counts = [], []
    for c in range(8):
        cut = cfg.replace(moe=dataclasses.replace(cfg.moe, held=2,
                                                  first_held=2 * c))
        y, aux = apply_moe(share(p, 2 * c, 2), x, cut)
        outs.append(y)
        counts.append(aux["expert_counts"])
        for key in ("aux_loss", "z_loss"):
            assert float(aux[key]) == pytest.approx(float(whole_aux[key]),
                                                    rel=1e-6)
    np.testing.assert_allclose(np.asarray(sum(outs)), np.asarray(whole),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.concatenate(counts),
                                  np.asarray(whole_aux["expert_counts"]))
    want, _ = ref_moe(p, x, cfg.moe)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("held,first_held", [(0, 0), (2, 0), (8, 4)])
def test_no_token_is_dropped_when_every_token_picks_the_same_experts(
        held, first_held):
    """A router that sends every token to experts 0..3: each of them gets
    all 48 tokens, which a capacity of 1.25 x the even share would have cut
    to 15; every token still matches the reference."""
    cfg = layer_cfg(held=held, first_held=first_held)
    p = layer_params(seed=4)
    w = p["router"]["w"].at[0].set(jnp.where(jnp.arange(16) < 4, 8.0, -8.0))
    p = {"router": {"w": w}, "experts": p["experts"]}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, cfg.d_model))
    x = x.at[..., 0].set(3.0)
    n = cfg.moe.n_held
    p_held = share(p, first_held, n)
    y, aux = apply_moe(p_held, x, cfg)
    counts = np.zeros(16, int)
    counts[:4] = 48
    np.testing.assert_array_equal(np.asarray(aux["expert_counts"]),
                                  counts[first_held:first_held + n])
    want, _ = ref_moe(p_held, x, cfg.moe)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    if held and first_held >= 4:            # none of the picks held here
        assert float(jnp.max(jnp.abs(y))) == 0.0


def test_gradients_of_a_share_match_the_reference():
    cfg = layer_cfg(held=4, first_held=8)
    p = share(layer_params(seed=9), 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))

    def prog(p, x):
        y, aux = apply_moe(p, x, cfg)
        return jnp.sum(jnp.sin(y)) + aux["aux_loss"] + aux["z_loss"]

    def ref(p, x):
        y, extra = ref_moe(p, x, cfg.moe)
        return jnp.sum(jnp.sin(y)) + extra

    got = jax.grad(prog, (0, 1))(p, x)
    want = jax.grad(ref, (0, 1))(p, x)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-5, rtol=1e-4)


def test_held_experts_must_lie_inside_the_router():
    with pytest.raises(ValueError, match="held experts"):
        layer_cfg(held=8, first_held=12)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------


def test_yarn_frequencies_at_mellums_numbers():
    """head_dim 128, theta 500000, factor 16 over 8192 positions, beta 32 /
    1: the correction range is pairs 18..35; below it the original
    frequency, above it the original over 16, a linear ramp between."""
    yarn = get_config("mellum2-12b").yarn
    d, theta = 128, 500_000.0
    got = np.asarray(yarn_freqs(d, theta, yarn), np.float64)
    base = 1.0 / theta ** (np.arange(0, d, 2) / d)
    assert math.floor(d * math.log(8192 / (2 * math.pi * 32))
                      / (2 * math.log(theta))) == 18
    assert math.ceil(d * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(theta))) == 35
    ramp = np.clip((np.arange(64) - 18) / 17, 0, 1)
    np.testing.assert_allclose(got, base / 16 * ramp + base * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(got[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(got[35:], base[35:] / 16, rtol=1e-6)
    # cos and sin carry the attention factor: q.k scores its square
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 1, d))
    r = apply_rope(q, jnp.arange(4)[None], theta, yarn)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(r), axis=-1),
        1.2772588722239782 * np.linalg.norm(np.asarray(q), axis=-1),
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ref_rope(q, theta, yarn)),
                               np.asarray(r), atol=1e-5)
