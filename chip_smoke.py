#!/usr/bin/env python3
"""Smoke run of the training path on a TPU, in one process.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # a four-chip host: 2x2 mesh phase only

One chip: granite-8b at its published widths, cut to 1 layer, trains 4
steps (the first is warm-up) at batch 2 x 4096 through
``repro.launch.train.run``; then each Pallas kernel is compiled with Mosaic,
run once at real widths and compared with ``repro.kernels.ref``.

Four chips: the same 1-layer step on the 2x2 mesh and on one device of the
host, whose losses must agree; then granite-8b at 4 layers, batch 8 x 4096,
which only the 2x2 mesh holds.

The script exits non-zero, printing no result, when JAX finds no TPU or any
phase fails.  Its last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
import argparse
import gc
import json
import math
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp else

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import flash_attention, rglru_scan  # noqa: E402
from repro.launch.cache import CACHE_DIR, use_compile_cache  # noqa: E402
from repro.launch.train import build_argparser, run  # noqa: E402

ARCH = "granite-8b"
STEPS = 4       # the first is warm-up
GRANITE_1L = ["--arch", ARCH, "--full", "--layers", "1", "--seq", "4096",
              "--batch", "2", "--steps", str(STEPS), "--log-every", "1"]
GRANITE_4L = ["--arch", ARCH, "--full", "--layers", "4", "--seq", "4096",
              "--batch", "8", "--steps", str(STEPS), "--log-every", "1"]

# bf16 attention: inputs, probabilities and output are each rounded to 8
# significant bits (2^-8 = 3.9e-3 relative) once, as tests/test_kernels.py
# allows for bf16.
ATTN_TOL = (2e-2, 2e-2)
# f32 recurrence: the reference's associative scan multiplies in another
# order, so the two differ by f32 rounding only (tests/test_kernels.py).
SCAN_TOL = (3e-5, 3e-5)
# 2x2 mesh vs one chip, bf16 activations: partial sums meet across chips
# after rounding to 8 significant bits; the loss is a mean over 8192 token
# losses, so it keeps far less than one rounding's error (3.9e-3), also
# after 3 AdamW steps at lr 3e-4.
MESH_LOSS_RTOL = 4e-3


def train_phase(argv, devices=None) -> dict:
    args = build_argparser().parse_args(argv)
    cfg = get_config(args.arch, smoke=args.smoke)
    published = get_config(args.arch).n_layers
    print(f"config {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads} "
          f"(kv {cfg.n_kv}), head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}; cut: layers {published} -> {args.layers} "
          f"(so f32 params and AdamW state fit the HBM); batch "
          f"{args.batch} x {args.seq}", flush=True)
    res = run(args, devices)
    losses = res["losses"]
    print(f"mesh {res['mesh']}; compile {res['compile_s']:.2f} s; "
          f"step wall s after warm-up {res['step_s'][1:]}; losses {losses}",
          flush=True)
    if len(losses) < STEPS or not all(map(math.isfinite, losses)):
        raise SystemExit(f"{args.arch}: losses not finite: {losses}")
    gc.collect()   # the step's arrays are gone before the next phase
    return res


def assert_mosaic(name: str, compiled) -> None:
    if "tpu_custom_call" not in compiled.as_text():
        raise SystemExit(f"{name}: no Mosaic kernel in the compiled "
                          "program (it would be interpreted)")


def kernel_check(name, fn, ref_fn, args, tol) -> None:
    atol, rtol = tol
    compiled = jax.jit(fn).lower(*args).compile()
    assert_mosaic(name, compiled)
    out = jax.block_until_ready(compiled(*args)).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_fn)(*[a.astype(jnp.float32) for a in args])
    err = jnp.abs(out - want)
    max_err = float(jnp.max(err))
    ratio = float(jnp.max(err / (atol + rtol * jnp.abs(want))))
    print(f"kernel {name}: max |err| vs ref.py {max_err:.3e}; tolerance "
          f"|err| <= {atol} + {rtol}|ref| (worst {ratio:.3f} of it)",
          flush=True)
    if not ratio <= 1.0:
        raise SystemExit(f"{name}: error {max_err} beyond tolerance")


def kernel_phase(attn_shapes, scan_shape) -> None:
    """``attn_shapes``: (b, s, h, kv, d, window) cases; ``scan_shape``:
    (b, s, r)."""
    key = jax.random.PRNGKey(0)
    for b, s, h, kv, d, window in attn_shapes:
        kq, kk, kv_ = jax.random.split(jax.random.fold_in(key, d), 3)
        q = jax.random.normal(kq, (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(kk, (b, s, kv, d), jnp.bfloat16)
        v = jax.random.normal(kv_, (b, s, kv, d), jnp.bfloat16)
        kernel_check(
            f"flash_attention {(b, s, h, kv, d)} window {window}",
            lambda q, k, v, w=window: flash_attention(q, k, v, True, w),
            lambda q, k, v, w=window: ref.flash_attention_ref(
                q, k, v, causal=True, window=w),
            (q, k, v), ATTN_TOL)
    ka, kb = jax.random.split(jax.random.fold_in(key, 1))
    a = jax.nn.sigmoid(jax.random.normal(ka, scan_shape)) * 0.2 + 0.8
    bb = 0.1 * jax.random.normal(kb, scan_shape)
    kernel_check(f"rglru_scan {tuple(scan_shape)}", rglru_scan,
                 ref.rglru_scan_ref, (a, bb), SCAN_TOL)


def four_chip_phase(one_layer, deep) -> None:
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, found "
                          f"{len(devices)}")
    one = train_phase(one_layer, devices[:1])
    mesh = train_phase(one_layer)
    worst = max(abs(a - b) / abs(a)
                for a, b in zip(one["losses"], mesh["losses"]))
    print(f"2x2 mesh vs one chip: max relative loss difference {worst:.3e}, "
          f"tolerance {MESH_LOSS_RTOL}", flush=True)
    if not worst <= MESH_LOSS_RTOL:
        raise SystemExit(f"2x2 losses {mesh['losses']} differ from one "
                         f"chip's {one['losses']}")
    train_phase(deep)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh phase (a four-chip host)")
    args = ap.parse_args()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU, only {device}")
    print(f"device {device}", flush=True)

    use_compile_cache()
    hits = {"requests": 0, "hits": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            hits["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            hits["hits"] += 1

    jax.monitoring.register_event_listener(count)

    if args.four_chips:
        four_chip_phase(GRANITE_1L, GRANITE_4L)
    else:
        train_phase(GRANITE_1L)
        kernel_phase([(1, 4096, 32, 8, 128, 0), (1, 4096, 10, 1, 256, 2048)],
                     (2, 4096, 2560))
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    print(f"compile cache {cache}: {hits['hits']} hits of "
          f"{hits['requests']} requests", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
