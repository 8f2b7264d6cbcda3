"""Fused flash attention against the naive score chain, timed on one chip.

Two sweeps, each printing one JSON line per measurement and writing all of
them to ``chiprun_out/attention_sweep.json``:

``--kernel``  attention alone for each head layout of the zoo that the
              fused kernel takes (``kernels.flash_attention.fits``), at
              several sequence lengths: the gradient of a rematerialized
              attention call (forward, its recomputation, backward: what
              the train step runs), naive (``layers.mha_logits_to_out``,
              f32 scores) against ``kernels.ops.flash_attention``.
``--step``    the granite-8b train step cut to one layer (the benchmark's
              ``granite-8b-1l``), with ``layers.FUSED_ATTENTION_MIN_SEQ``
              (granite's group of 4 heads) set so that attention takes the
              naive chain or the kernel.

Times are medians of ``--iters`` calls after two warm-up calls, each call
ended by ``block_until_ready``.  Run on a TPU:

    python3 benchmarks/attention_sweep.py --kernel --step
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ARCH_IDS, get_config  # noqa: E402
from repro.kernels.flash_attention import block_sizes, fits  # noqa: E402
from repro.kernels.ops import flash_attention  # noqa: E402
from repro.models import layers  # noqa: E402

OUT = os.path.join("chiprun_out", "attention_sweep.json")


def median_ms(fn, args, iters: int) -> float:
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return statistics.median(ts) * 1e3


def zoo_layouts():
    """(arch, heads, kv heads, head_dim) for each causal self-attention
    layout the kernel takes; the first arch of a shared layout names it."""
    seen = {}
    for a in ARCH_IDS:
        c = get_config(a)
        lay = (c.n_heads, c.n_kv, c.head_dim)
        if ({"attn", "moe"} & set(c.pattern) and lay not in seen
                and fits(4096, 4096, c.n_heads // c.n_kv, c.head_dim)):
            seen[lay] = a
    return [(a, *lay) for lay, a in seen.items()]


def kernel_sweep(seqs, tokens: int, iters: int):
    for arch, h, kv, d in zoo_layouts():
        cfg = get_config(arch)
        for s in seqs:
            b = max(1, tokens // s)
            ks = jax.random.split(jax.random.PRNGKey(s), 4)
            q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
            k = jax.random.normal(ks[1], (b, s, kv, d), jnp.bfloat16)
            v = jax.random.normal(ks[2], (b, s, kv, d), jnp.bfloat16)
            do = jax.random.normal(ks[3], (b, s, h, d), jnp.bfloat16)
            mask = layers.causal_mask(s, s)
            impls = {
                "naive": lambda q, k, v: layers.mha_logits_to_out(
                    q, k, v, mask, cfg),
                "fused": lambda q, k, v: flash_attention(q, k, v, True, 0),
            }
            row = dict(sweep="kernel", arch=arch, heads=h, kv=kv,
                       head_dim=d, batch=b, seq=s,
                       tiles=block_sizes(s, s, h // kv, d),
                       fits=fits(s, s, h // kv, d))
            for name, attn in impls.items():
                if name == "fused" and not row["fits"]:
                    continue
                body = jax.checkpoint(
                    attn, policy=jax.checkpoint_policies.nothing_saveable)

                def grads(q, k, v, do, body=body):
                    return jax.grad(
                        lambda q, k, v: jnp.sum(
                            (body(q, k, v) * do).astype(jnp.float32)),
                        (0, 1, 2))(q, k, v)
                try:
                    row[name + "_ms"] = median_ms(jax.jit(grads),
                                                  (q, k, v, do), iters)
                except Exception as e:  # out of memory at long S, etc.
                    row[name + "_err"] = str(e)[:200]
            yield row


def step_sweep(shapes, iters: int):
    from repro.configs import get_optimizer_name
    from repro.configs.shapes import train_batch_specs
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import jit_train_step, train_in_shardings
    from repro.models import init_params
    from repro.optim import make_optimizer

    cfg = get_config("granite-8b").replace(
        n_layers=1, tie_embeddings=True, rope_theta=1e7)
    opt = make_optimizer(get_optimizer_name("granite-8b"), lr=3e-4)
    mesh = make_mesh(jax.devices()[:1])
    for b, s in shapes:
        specs = train_batch_specs(cfg, b, s)
        in_sh, _, _ = train_in_shardings(cfg, opt, specs, mesh)
        p_sh, o_sh, b_sh = in_sh
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (b, s), 0, cfg.vocab, jnp.int32)
        batch = jax.device_put({"tokens": toks,
                                "labels": jnp.roll(toks, -1, axis=1)}, b_sh)
        row = dict(sweep="step", batch=b, seq=s)
        for name, min_seq in (("naive", 1 << 30), ("fused", 0)):
            layers.FUSED_ATTENTION_MIN_SEQ = min_seq
            params = jax.jit(lambda k: init_params(k, cfg),
                             out_shardings=p_sh)(key)
            state = [params, jax.jit(opt.init, out_shardings=o_sh)(params)]
            t = time.perf_counter()
            step = jit_train_step(cfg, opt, in_sh, mesh).lower(
                params, state[1], specs).compile()
            row[name + "_compile_s"] = time.perf_counter() - t
            row[name + "_fused"] = "flash_attention_fwd" in step.as_text()

            def run(batch):
                state[0], state[1], m = step(state[0], state[1], batch)
                return m["loss"]
            row[name + "_ms"] = median_ms(run, (batch,), iters)
            del state, params
        yield row


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--kernel", action="store_true")
    ap.add_argument("--step", action="store_true")
    ap.add_argument("--seqs", default="256,512,1024,2048")
    ap.add_argument("--tokens", type=int, default=8192,
                    help="batch x seq of the kernel sweep")
    ap.add_argument("--shapes", default="32x256,16x512,8x1024",
                    help="batch x seq of the step sweep")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    rows = []
    sweeps = []
    if args.kernel:
        sweeps.append(kernel_sweep([int(x) for x in args.seqs.split(",")],
                                   args.tokens, args.iters))
    if args.step:
        sweeps.append(step_sweep(
            [tuple(map(int, x.split("x"))) for x in args.shapes.split(",")],
            args.iters))
    for sweep in sweeps:
        for row in sweep:
            print(json.dumps(row), flush=True)
            rows.append(row)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(dict(device=jax.devices()[0].device_kind, rows=rows), f,
                  indent=1)


if __name__ == "__main__":
    main()
