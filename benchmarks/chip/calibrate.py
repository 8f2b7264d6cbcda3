#!/usr/bin/env python3
"""Readings that the limits of a cell are set from, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 ... \\
        [--controls 3]

For every seed: the program's first three steps as a run's set-up makes
them, against the float32 reference (the lower readings).  For the first
``--controls`` seeds also, each against the same reference:

- ``control``: the reference with every matrix product's operands rounded
  to float8_e4m3fn, the type below the configuration's bfloat16;
- ``half_batch``: the reference over the first half of the rows only, the
  fault of a step that drops half the batch and takes the mean over the
  rest (on a 2x2 mesh also what replica 0 holds when the data-parallel
  exchange is left out).

A state left unchanged reads 1 on both norm gaps by construction and needs
no run.  Prints one JSON line per seed, then a summary line.  Nothing here
runs in the benchmark's own runs.
"""
import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from chipbench import compare, harness, traffic, weights
    from chipbench.reference import Reference
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(args.workload)
    devices = jax.devices()[: cell.chips]
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        sys.exit(f"calibrate.py: {cell.name} needs {cell.chips} chips")
    m, mix, hp = cell.config["model"], cell.mix, cell.config["optimizer"]
    prog = harness.Program(cell.config, mix, devices)
    refs = {"reference": Reference(m, hp, devices, mix["batch"]),
            "control": Reference(m, hp, devices, mix["batch"],
                                 low=jnp.float8_e4m3fn),
            "half_batch": Reference(m, hp, devices, mix["batch"],
                                    rows=mix["batch"] // 2)}
    names = list(weights.flatten(weights.shapes(m)))

    rows = []
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        key = weights.seed_key(seed)
        data = traffic.source(prog.cfg, mix, seed)
        params, opt_state, got, fed = harness.program_first_steps(
            prog, data, key, hp["b1"])
        del params, opt_state
        gc.collect()
        ref = refs["reference"].run(key, fed)
        row = {"seed": seed, "program": compare.gaps(got, ref),
               "leaves": {n: [float(got["grad_norms"][i]),
                              float(ref["grad_norms"][i]),
                              float(got["delta_norms"][i]),
                              float(ref["delta_norms"][i])]
                          for i, n in enumerate(names)},
               "losses": got["losses"], "ref_losses": ref["losses"],
               "repeated_rows": compare.repeated_rows(fed),
               "leaves_left_out": int((~compare.moved(ref)).sum())}
        if i < args.controls:
            for name in ("control", "half_batch"):
                row[name] = compare.gaps(refs[name].run(key, fed), ref)
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)

    summary = {"workload": cell.name, "seeds": len(rows),
               "device_kind": devices[0].device_kind}
    for n in rows[0]["program"]:
        summary[n] = {
            "lower": max(r["program"][n] for r in rows),
            **{f"upper_{k}": min(r[k][n] for r in rows if k in r)
               for k in ("control", "half_batch") if args.controls}}
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
