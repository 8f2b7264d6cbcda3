#!/usr/bin/env python3
"""The chip benchmark: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  Cells, metrics and bounds are in
``BENCHMARK.json``; each configuration, traffic mix, limit set and metric
reader is a file under ``benchmarks/chip/`` found by its name.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``, each number that decides ``correct`` beside its limit.  The
command exits non-zero and prints no result when JAX finds no accelerator
or fewer chips than the cell asks for.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs to /tmp else


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import jax
    from chipbench import harness
    from repro.launch.cache import use_compile_cache

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform == "cpu":
        sys.exit("run.py: JAX finds no accelerator")
    if len(devices) < cell.chips:
        sys.exit(f"run.py: {cell.name} needs {cell.chips} chips, JAX finds "
                 f"{len(devices)}")
    use_compile_cache()
    # Most of a step's small programs compile in under JAX's default 1 s
    # threshold and would be compiled again on every start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = harness.run_cell(cell, devices[: cell.chips], args.seed,
                              args.seconds, bool(args.trace), T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
