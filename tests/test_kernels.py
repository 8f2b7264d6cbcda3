"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import (BLOCK_K, BLOCK_Q,
                                           flash_attention_bwd,
                                           flash_attention_fwd)
from repro.kernels.ops import flash_attention, rglru_scan

KEY = jax.random.PRNGKey(7)


def qkv(b, s, h, kv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d)).astype(dtype)
    return q, k, v


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,kv,d", [
        (1, 128, 1, 1, 64),
        (2, 256, 4, 2, 64),
        (1, 512, 8, 8, 128),
        (2, 384, 6, 2, 64),      # non-power-of-two seq (divisible blocks)
        (1, 256, 4, 1, 128),     # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, b, s, h, kv, d, dtype):
        q, k, v = qkv(b, s, h, kv, d, dtype)
        out = flash_attention(q, k, v, True, 0)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=TOL[dtype], rtol=TOL[dtype])

    @pytest.mark.parametrize("window", [64, 128, 256])
    def test_sliding_window(self, window):
        q, k, v = qkv(1, 512, 4, 2, 64, jnp.float32)
        out = flash_attention(q, k, v, True, window)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_noncausal(self):
        q, k, v = qkv(2, 256, 4, 4, 64, jnp.float32)
        out = flash_attention(q, k, v, False, 0)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("b,s,t,h,kv,d,window,dtype,blocks", [
        # the first case is the one this test started from
        (1, 256, 256, 2, 2, 64, 0, jnp.float32, None),
        *[(1, s, 256, 4, 4 // group, 64, window, dtype, (64, 128))
          for group in (1, 4) for window in (0, 96) for s in (256, 128)
          for dtype in (jnp.float32, jnp.bfloat16)],
        (2, 128, 256, 4, 1, 64, 0, jnp.bfloat16, (128, 64)),
    ])
    def test_grads_match_reference(self, b, s, t, h, kv, d, window, dtype,
                                   blocks):
        """The backward kernels' dq, dk, dv against the reference's VJP,
        and the saved logsumexp against the reference scores'; queries
        right-aligned against the keys when S < T."""
        ks = jax.random.split(KEY, 4)
        q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
        k = jax.random.normal(ks[1], (b, t, kv, d)).astype(dtype)
        v = jax.random.normal(ks[2], (b, t, kv, d)).astype(dtype)
        do = jax.random.normal(ks[3], (b, s, h, d)).astype(dtype)
        bq, bk = blocks or (BLOCK_Q, BLOCK_K)
        if blocks is None:
            # through the op's custom VJP: the test's original inputs,
            # loss and tolerance
            q, k, v = qkv(b, s, h, kv, d, dtype)

            def f_kernel(q, k, v):
                return jnp.sum(flash_attention(q, k, v, True, 0) ** 2)

            def f_ref(q, k, v):
                return jnp.sum(ref.flash_attention_ref(q, k, v,
                                                       causal=True) ** 2)

            g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
            g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
            for a, w in zip(g1, g2):
                np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                           atol=1e-4, rtol=1e-4)
            _, lse = flash_attention_fwd(q, k, v, True, window,
                                         interpret=True)
        else:
            out, lse = flash_attention_fwd(q, k, v, True, window, bq, bk,
                                           interpret=True)
            got = flash_attention_bwd(q, k, v, out, lse, do, True, window,
                                      bq, bk, interpret=True)
            want_out, ref_vjp = jax.vjp(
                lambda q, k, v: ref.flash_attention_ref(q, k, v, True,
                                                        window), q, k, v)
            # relative to each array's largest element: bf16 rounds the
            # operands, so small elements carry the large ones' error
            tol = 1e-4 if dtype == jnp.float32 else 2e-2
            for a, w in zip((out, *got), (want_out, *ref_vjp(do))):
                a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
                np.testing.assert_allclose(a, w, rtol=0,
                                           atol=tol * np.abs(w).max())
        g = h // kv
        logits = jnp.einsum(
            "bskgd,btkd->bkgst", q.reshape(b, s, kv, g, d).astype(jnp.float32),
            k.astype(jnp.float32)) / np.sqrt(d)
        qi = jnp.arange(s)[:, None] + (t - s)
        ki = jnp.arange(t)[None, :]
        live = (ki <= qi) & ((ki > qi - window) if window else True)
        want_lse = jax.nn.logsumexp(jnp.where(live, logits, -jnp.inf), -1)
        np.testing.assert_allclose(np.asarray(lse[:, :, 0]),
                                   np.asarray(want_lse.reshape(b, h, s)),
                                   rtol=1e-6, atol=1e-5)

    def test_jit_compatible(self):
        q, k, v = qkv(1, 256, 2, 2, 64, jnp.float32)
        out = jax.jit(lambda *a: flash_attention(*a, True, 0))(q, k, v)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestRglruScan:
    @pytest.mark.parametrize("b,s,r", [
        (1, 256, 128), (2, 512, 256), (3, 256, 384),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, b, s, r, dtype):
        ks = jax.random.split(KEY, 2)
        a = (jax.nn.sigmoid(jax.random.normal(ks[0], (b, s, r))) * 0.2
             + 0.8).astype(dtype)
        bb = (0.1 * jax.random.normal(ks[1], (b, s, r))).astype(dtype)
        h = rglru_scan(a.astype(jnp.float32), bb.astype(jnp.float32))
        want = ref.rglru_scan_ref(a.astype(jnp.float32),
                                  bb.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    def test_batched_leading_dims(self):
        ks = jax.random.split(KEY, 2)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 2, 256, 128)))
        b = 0.1 * jax.random.normal(ks[1], (2, 2, 256, 128))
        h = rglru_scan(a, b)
        want = ref.rglru_scan_ref(a, b)
        np.testing.assert_allclose(np.asarray(h), np.asarray(want),
                                   atol=3e-5, rtol=3e-5)

    def test_grad_adjoint(self):
        ks = jax.random.split(KEY, 2)
        a = jax.nn.sigmoid(jax.random.normal(ks[0], (1, 256, 128))) * 0.5
        b = 0.1 * jax.random.normal(ks[1], (1, 256, 128))
        ga = jax.grad(lambda a: jnp.sum(rglru_scan(a, b) ** 2))(a)
        gr = jax.grad(lambda a: jnp.sum(ref.rglru_scan_ref(a, b) ** 2))(a)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(gr),
                                   atol=1e-4, rtol=1e-4)

    def test_decay_stability(self):
        """|a| < 1 keeps h bounded over long sequences."""
        a = jnp.full((1, 2048, 64), 0.99)
        b = jnp.ones((1, 2048, 64)) * 0.01
        h = rglru_scan(a, b)
        assert float(jnp.max(jnp.abs(h))) < 2.0
