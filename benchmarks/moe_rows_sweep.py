"""An MoE layer's row movement between token order and expert order, timed
on one chip, three ways:

``gather``  the gathers over the whole T*min(k, held) buffer (the path a
            mesh of several devices keeps: ``models.moe._gather_rows``,
            the held mask, the combine einsum);
``fill``    the same gathers with the rows past the held experts' made
            out of range, so ``mode="fill"`` has nothing to read there;
``kernels`` ``kernels.ops.moe_dispatch`` and ``moe_combine`` (the Pallas
            row kernels of ``kernels.moe_rows``).

At mellum2-12b-4l.train_4k_b8's shapes (T = 8 x 4096 tokens, d 2304, top 8
of 64 experts; 8 held: a random router's live share is 1/8; 64 held: every
row is live), each prints the forward (dispatch, then combine of the same
rows) and its gradient (forward and backward), and the largest difference
of each path's results from ``gather``'s, one JSON line each, all written
to ``chiprun_out/moe_rows_sweep.json``.  With ``--ops`` the kernels are
also timed one call each.  Times are medians of ``--iters`` calls after
two warm-up calls, each ended by ``block_until_ready``.  Run on a TPU:

    python3 benchmarks/moe_rows_sweep.py --ops
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

from repro.kernels import moe_rows, ops  # noqa: E402
from repro.models.config import MoEConfig  # noqa: E402
from repro.models.moe import _gather_rows, _sort_by_expert  # noqa: E402

OUT = os.path.join("chiprun_out", "moe_rows_sweep.json")


def median_ms(fn, args, iters: int) -> float:
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return statistics.median(ts) * 1e3


def routing(seed: int, t: int, k: int, experts: int, held: int):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    idx = jnp.argsort(jax.random.uniform(keys[0], (t, experts)),
                      axis=1)[:, :k]
    order, slot, mask = _sort_by_expert(
        idx, MoEConfig(num_experts=experts, top_k=k, held=held))
    live = jnp.sum(mask, dtype=jnp.int32)
    gate = jax.random.uniform(keys[1], (t, k), minval=0.05)
    gate = gate / jnp.sum(gate, axis=1, keepdims=True)
    return order, slot, mask, live, gate


def paths(t, k, rows):
    def gather(xt, gate, order, slot, mask, live, fill):
        order = jnp.pad(order, (0, max(rows - t * k, 0)),
                        constant_values=t * k)[:rows]
        if fill:
            dead = jnp.arange(rows) >= live
            order = jnp.where(dead, t * k, order)
            slot = jnp.where(mask, slot, rows)
        xs = _gather_rows(xt, order, slot, k)
        y = _gather_rows(xs, slot, order, 1)
        y = jnp.where(mask[:, None], y, 0).reshape(t, k, -1)
        return jnp.einsum("tkd,tk->td", y, gate.astype(xt.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(xt.dtype)

    def kernels(xt, gate, order, slot, mask, live, fill):
        del fill
        held_slot = jnp.where(mask, slot, -1).reshape(t, k)
        xs = ops.moe_dispatch(xt, order, live, held_slot, rows)
        return ops.moe_combine(xs, gate, order, live, held_slot)

    return {"gather": (gather, False), "fill": (gather, True),
            "kernels": (kernels, False)}


def layer_sweep(seed, t, d, k, experts, held, iters):
    order, slot, mask, live, gate = routing(seed, t, k, experts, held)
    rows = t * min(k, held)
    xt = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, d),
                           jnp.bfloat16)
    ct = jax.random.normal(jax.random.PRNGKey(seed + 2), (t, d),
                           jnp.bfloat16)
    routed = (order, slot, mask, live)
    got, out = {}, []
    for name, (fn, fill) in paths(t, k, rows).items():
        fwd = jax.jit(lambda xt, gate, ct, *r, fn=fn, fill=fill:
                      fn(xt, gate, *r, fill))

        def loss(xt, gate, ct, *r, fn=fn, fill=fill):
            y = fn(xt, gate, *r, fill).astype(jnp.float32)
            return jnp.sum(y * ct.astype(jnp.float32))

        # every array an argument: XLA folds no kernel over constants
        grad = jax.jit(jax.grad(loss, (0, 1)))
        args = (xt, gate, ct) + routed
        res = (fwd(*args),) + tuple(grad(*args))
        got[name] = res
        row = {"held": held, "live_rows": int(live), "rows": rows,
               "path": name,
               "forward_ms": median_ms(fwd, args, iters),
               "grad_ms": median_ms(grad, args, iters)}
        if name != "gather":
            for what, a, b in zip(("out", "dx", "dgate"), res,
                                  got["gather"]):
                row[f"max_diff_{what}"] = float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def ops_sweep(seed, t, d, k, experts, held, iters):
    order, slot, mask, live, gate = routing(seed, t, k, experts, held)
    rows = t * min(k, held)
    xt = jax.random.normal(jax.random.PRNGKey(seed + 1), (t, d),
                           jnp.bfloat16)
    ys = jax.random.normal(jax.random.PRNGKey(seed + 3), (rows, d),
                           jnp.bfloat16)
    held_slot = jnp.where(mask, slot, -1).reshape(t, k)
    weight = jnp.where(held_slot >= 0, gate, 0.0)
    index = jnp.pad(order, (0, max(rows - t * k, 0)))[:rows] // k
    scale = jnp.ones((rows,), jnp.float32)
    interpret = jax.default_backend() == "cpu"
    stage = jax.jit(lambda y, n: moe_rows.stage_rows(
        y, n, interpret=interpret))
    kw = {"interpret": interpret, "dtype": jnp.bfloat16}
    calls = {
        "stage_rows_tokens": (stage, (xt, t)),
        "stage_rows_live": (stage, (ys, live)),
        "dispatch_rows": (jax.jit(lambda s, i, n: moe_rows.dispatch_rows(
            s, i, n, **kw)), (stage(xt, t), index, live)),
        "dispatch_rows_dot": (jax.jit(
            lambda s, i, n, sc, y: moe_rows.dispatch_rows(
                s, i, n, sc, y, **kw)),
            (stage(xt, t), index, live, scale, ys)),
        "combine_rows": (jax.jit(lambda s, sl, w: moe_rows.combine_rows(
            s, sl, w, **kw)), (stage(ys, live), held_slot, weight)),
    }
    out = []
    for name, (fn, args) in calls.items():
        row = {"held": held, "live_rows": int(live), "op": name,
               "ms": median_ms(fn, args, iters)}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ops", action="store_true",
                    help="also time each kernel alone")
    ap.add_argument("--tokens", type=int, default=8 * 4096)
    ap.add_argument("--d", type=int, default=2304)
    ap.add_argument("--top-k", type=int, default=8)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--held", type=int, nargs="+", default=[8, 64])
    ap.add_argument("--seed", type=int, default=3171700001)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.device_kind, "platform": dev.platform}),
          flush=True)
    rows = []
    for held in args.held:
        shape = (args.seed % 2**31, args.tokens, args.d, args.top_k,
                 args.experts, held, args.iters)
        rows += layer_sweep(*shape)
        if args.ops:
            rows += ops_sweep(*shape)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
