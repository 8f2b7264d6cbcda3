import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (sharding
lowers, collectives are supported, memory fits) and extracts the roofline
inputs:  ``compiled.cost_analysis()`` (FLOPs / HBM bytes),
``compiled.memory_analysis()`` (bytes per device) and the collective
schedule parsed from the compiled HLO text.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
        --shape train_4k [--multi-pod] [--out results.json]
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""
import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional  # noqa: E402

import jax               # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, get_config,  # noqa: E402
                           get_optimizer_name, input_specs, shape_applicable)
from repro.core import hlo_analysis as ha  # noqa: E402
from repro.core import hlo_static as hs  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import steps as S  # noqa: E402
from repro.optim import make_optimizer  # noqa: E402
from repro.parallel.sharding import ShardingRules  # noqa: E402


def _mem_analysis_dict(compiled) -> Dict:
    try:
        m = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(m, k, None)
        if v is not None:
            out[k] = int(v)
    out["total_bytes_per_device"] = (
        out.get("argument_size_in_bytes", 0)
        + out.get("output_size_in_bytes", 0)
        + out.get("temp_size_in_bytes", 0)
        - out.get("alias_size_in_bytes", 0))
    return out


def dryrun_cell(arch: str, shape: str, multi_pod: bool = False,
                hillclimb: Optional[Dict] = None, optimized: bool = False,
                verbose: bool = True) -> Dict:
    """Lower + compile one cell; returns the roofline record."""
    cfg = get_config(arch, optimized=optimized)
    if hillclimb:
        cfg = cfg.replace(**hillclimb)
    ok, reason = shape_applicable(cfg, shape)
    rec: Dict = {"arch": arch, "shape": shape, "optimized": optimized,
                 "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec

    sp = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = ShardingRules()
    chips = int(mesh.devices.size)
    specs = input_specs(cfg, shape)
    t0 = time.time()
    try:
        if sp.kind == "train":
            opt = make_optimizer(get_optimizer_name(arch), lr=1e-3)
            in_shardings, pshapes, oshapes = S.train_in_shardings(
                cfg, opt, specs, mesh, rules)
            jitted = S.jit_train_step(cfg, opt, in_shardings, mesh, rules)
            lowered = jitted.lower(pshapes, oshapes, specs)
            tokens = sp.global_batch * sp.seq_len
            model_flops = ha.model_flops_train(cfg, tokens)
        elif sp.kind == "prefill":
            from repro.models.transformer import param_shapes
            from repro.parallel.sharding import params_shardings
            step = S.make_prefill_step(cfg, mesh, rules)
            pshapes = param_shapes(cfg)
            in_shardings = (params_shardings(pshapes, mesh, rules),
                            S.batch_shardings(specs, mesh, rules))
            jitted = jax.jit(step, in_shardings=in_shardings)
            lowered = jitted.lower(pshapes, specs)
            tokens = sp.global_batch * sp.seq_len
            model_flops = ha.model_flops_train(cfg, tokens) / 3.0  # fwd only
        else:  # decode
            step = S.make_serve_step(cfg, mesh, rules)
            state_shapes = specs["state"]
            in_shardings, pshapes = S.serve_in_shardings(
                cfg, state_shapes, sp.global_batch, mesh, rules)
            jitted = jax.jit(step, in_shardings=in_shardings,
                             donate_argnums=(1,))
            lowered = jitted.lower(pshapes, state_shapes, specs["token"])
            model_flops = ha.model_flops_decode(cfg, sp.global_batch,
                                                sp.seq_len)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        return rec

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    hlo = compiled.as_text()
    # static profile: XLA's cost_analysis counts while (scan) bodies ONCE;
    # parse_hlo_profile applies known_trip_count multipliers (hlo_static.py)
    prof = hs.parse_hlo_profile(hlo)
    terms = ha.RooflineTerms(
        hlo_flops=prof.flops, hlo_bytes=prof.hbm_bytes,
        collective_bytes=float(prof.collective_wire_bytes), chips=chips,
        model_flops=model_flops)

    rec.update({
        "status": "ok",
        "kind": sp.kind,
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": _mem_analysis_dict(compiled),
        "cost": {k: float(v) for k, v in cost.items()
                 if isinstance(v, (int, float))},
        "collectives": {
            "bytes_by_kind": {k: int(v) for k, v in
                              prof.collective_by_kind.items()},
            "count_by_kind": prof.collective_count,
            "total_wire_bytes": int(prof.collective_wire_bytes),
        },
        "top_ops": [
            {"kind": o.kind, "name": o.name, "comp": o.comp,
             "flops": o.flops, "bytes": o.out_bytes + o.operand_bytes,
             "coll_bytes": o.coll_wire_bytes, "mult": o.mult}
            for o in prof.top_ops(12)],
        "roofline": terms.as_dict(),
    })
    if verbose:
        mem = rec["memory"].get("total_bytes_per_device", 0) / 2**30
        print(f"[{rec['mesh']}] {arch:22s} {shape:12s} ok "
              f"mem/dev={mem:6.2f}GiB t_comp={terms.t_compute*1e3:8.2f}ms "
              f"t_mem={terms.t_memory*1e3:8.2f}ms "
              f"t_coll={terms.t_collective*1e3:8.2f}ms "
              f"bound={terms.bottleneck:10s} mfu_bound={terms.mfu_bound:.2f}",
              flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="use the hillclimbed config variants (§Perf)")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch, shape) for both meshes")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    records = []
    if args.all:
        meshes = [False] if args.single_pod_only else [False, True]
        for mp in meshes:
            for arch in ARCH_IDS:
                for shape in SHAPES:
                    records.append(dryrun_cell(arch, shape, multi_pod=mp,
                                               optimized=args.optimized))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        rec = dryrun_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                          optimized=args.optimized)
        if rec["status"] == "error":
            print(rec["error"])
            print(rec.get("traceback", ""))
        records.append(rec)

    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_err = len(records) - n_ok - n_skip
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), "
          f"{n_err} errors")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out}")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
