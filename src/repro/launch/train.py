"""End-to-end training driver.

CPU-scale example (deliverable): train a reduced-config model for a few
hundred steps with checkpoint/restart fault tolerance:

    PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --smoke \
        --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a TPU the same entry point runs at published widths (``--full``), jitted
through the shardings of launch/steps.py on a (data, model) mesh laid over
every device present: one chip is (1, 1), a four-chip host (2, 2).  For
example, granite-8b cut to one layer on one v5e chip:

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --full \
        --layers 1 --seq 4096 --batch 2 --steps 3 --log-every 1

Fault tolerance:
  * checkpoints (params + optimizer + data-pipeline state) every
    --ckpt-every steps, atomic manifests, resume from LATEST on restart;
  * --fail-at N raises a simulated hard fault at step N (used by the tests
    to validate restart-equivalence);
  * async-SGD mode (--async-staleness) applies tau-stale gradients — the
    paper's training semantics;
  * --compress {int8,topk} runs gradient compression with error feedback
    on the DP reduction path.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import checkpoint as ckpt
from repro.configs import ARCH_IDS, get_config, get_optimizer_name
from repro.configs.shapes import train_batch_specs
from repro.data import SyntheticLM
from repro.launch.cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.launch.steps import (jit_train_step, make_grad_step,
                                train_in_shardings)
from repro.models import init_params
from repro.optim import (async_init, async_step, make_compressor,
                         make_optimizer)


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="gemma-7b")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced config (CPU scale)")
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--layers", type=int, default=0,
                    help="override layer count (0 = config default)")
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, default=-1,
                    help="simulate a hard fault at this step (testing)")
    ap.add_argument("--async-staleness", type=int, default=0,
                    help="PS-style async SGD with this staleness")
    ap.add_argument("--compress", choices=["", "int8", "topk"], default="")
    return ap


def run(args, devices=None) -> dict:
    """Train per ``args`` on a (data, model) mesh over ``devices`` (default:
    every device present).  Every step ends in ``block_until_ready``; the
    result holds the losses, the compile seconds of the train step and the
    wall seconds of each step."""
    cfg = get_config(args.arch, smoke=args.smoke)
    overrides = {}
    if args.layers:
        overrides["n_layers"] = args.layers
    if args.d_model:
        overrides["d_model"] = args.d_model
    if overrides:
        cfg = cfg.replace(**overrides)
    opt_name = args.optimizer or get_optimizer_name(args.arch)
    if opt_name == "adafactor" and args.smoke:
        opt_name = "adamw"
    opt = make_optimizer(opt_name, lr=args.lr)

    mesh = make_mesh(devices)
    specs = train_batch_specs(cfg, args.batch, args.seq)
    in_shardings, _, _ = train_in_shardings(cfg, opt, specs, mesh)
    p_sh, o_sh, b_sh = in_shardings

    data = SyntheticLM(cfg, args.batch, args.seq, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    params = jax.jit(lambda k: init_params(k, cfg), out_shardings=p_sh)(key)
    opt_state = jax.jit(opt.init, out_shardings=o_sh)(params)
    start_step = 0

    # resume
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree = {"params": params, "opt_state": opt_state}
        tree, meta = ckpt.restore(
            args.ckpt_dir, tree, mesh=mesh,
            shard_fn=lambda t, m: {"params": p_sh, "opt_state": o_sh})
        params, opt_state = tree["params"], tree["opt_state"]
        data.load_state_dict(meta["data_state"])
        start_step = int(meta["step"]) + 1
        print(f"resumed from step {start_step - 1}")

    use_async = args.async_staleness > 0
    compressor = make_compressor(args.compress) if args.compress else None
    comp_err = compressor.init(params) if compressor else None

    compile_s = None
    if use_async or compressor:
        grad_fn = jax.jit(make_grad_step(cfg, mesh))
        if use_async:
            astate = async_init(params, opt, args.async_staleness)
    else:
        t = time.perf_counter()
        step_fn = jit_train_step(cfg, opt, in_shardings, mesh).lower(
            params, opt_state, specs).compile()
        compile_s = time.perf_counter() - t

    losses, step_s = [], []
    t0 = time.time()
    tokens_per_step = args.batch * args.seq
    for step in range(start_step, args.steps):
        if step == args.fail_at:
            raise RuntimeError(f"simulated node failure at step {step}")
        batch = jax.block_until_ready(
            jax.device_put(data.next_batch(), b_sh))
        t = time.perf_counter()
        if use_async:
            grads, metrics = grad_fn(astate.params, batch)
            if compressor:
                payload, comp_err = compressor.compress(grads, comp_err)
                grads = compressor.decompress(payload)
            astate = async_step(astate, grads, opt, args.async_staleness)
            params = astate.params
        elif compressor:
            grads, metrics = grad_fn(params, batch)
            payload, comp_err = compressor.compress(grads, comp_err)
            grads = compressor.decompress(payload)
            params, opt_state = opt.update(grads, opt_state, params)
        else:
            params, opt_state, metrics = step_fn(params, opt_state, batch)
        jax.block_until_ready((params, opt_state, metrics))
        step_s.append(time.perf_counter() - t)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            tps = tokens_per_step * (step - start_step + 1) / max(dt, 1e-9)
            print(f"step {step:5d} loss {loss:8.4f} "
                  f"({tps:,.0f} tok/s)", flush=True)
        if args.ckpt_dir and (step % args.ckpt_every == 0
                              or step == args.steps - 1):
            ckpt.save(args.ckpt_dir, step,
                      {"params": params, "opt_state": opt_state},
                      metadata={"step": step,
                                "data_state": data.state_dict(),
                                "arch": args.arch})
            ckpt.cleanup(args.ckpt_dir, keep=3)

    result = {"first_loss": losses[0] if losses else None,
              "last_loss": losses[-1] if losses else None,
              "steps": len(losses), "losses": losses,
              "compile_s": compile_s, "step_s": step_s,
              "mesh": dict(mesh.shape)}
    print(f"done: loss {result['first_loss']:.4f} -> "
          f"{result['last_loss']:.4f} over {result['steps']} steps")
    return result


def main() -> None:
    use_compile_cache()
    run(build_argparser().parse_args())


if __name__ == "__main__":
    main()
