"""Batched serving driver: prefill + decode with the per-arch cache/state.

CPU-scale example:
    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
        --smoke --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.data import SyntheticLM
from repro.launch.cache import use_compile_cache
from repro.models import (init_decode_state, init_params,
                          precompute_cross_kv, serve_step)
from repro.models.transformer import _get_encoder_states


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(key, cfg)
    data = SyntheticLM(cfg, args.batch, args.prompt_len, seed=args.seed)
    batch = data.next_batch()
    prompts = batch["tokens"]

    max_len = args.prompt_len + args.gen
    state = init_decode_state(cfg, args.batch, max_len)
    if cfg.cross_len:
        enc = _get_encoder_states(params, batch, cfg)
        state = precompute_cross_kv(params, state,
                                    enc.astype(cfg.dtype), cfg)

    step = jax.jit(lambda p, s, t: serve_step(p, s, t, cfg),
                   donate_argnums=(1,))

    # prefill: feed prompt tokens through the decode path
    t0 = time.time()
    logits = None
    for i in range(args.prompt_len):
        logits, state = step(params, state, prompts[:, i])
    t_prefill = time.time() - t0

    # greedy decode
    out = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t0 = time.time()
    for _ in range(args.gen):
        out.append(np.asarray(tok))
        logits, state = step(params, state, tok)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    t_gen = time.time() - t0

    gen = np.stack(out, axis=1)
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill {args.prompt_len} tok in {t_prefill:.2f}s, "
          f"decode {args.gen} tok in {t_gen:.2f}s "
          f"({args.batch * args.gen / max(t_gen, 1e-9):,.1f} tok/s)")
    print("first generated ids:", gen[0, :12].tolist())


if __name__ == "__main__":
    main()
