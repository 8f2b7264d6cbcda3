"""One run of one cell: set-up, the measured window, the comparison with the
reference, and the readings of every metric.

Everything a cell is made of is found by name in files: its entry in
``BENCHMARK.json``, ``configs/<config>.json`` (the file the entry names),
``traffic/<traffic>.json``, ``limits/<workload>.json``, one reader
``metrics/<metric>.py`` per metric and one reference block
``chipbench/blocks/<kind>.py`` per layer kind of the configuration's
``model.pattern``.  Adding a cell, a metric or a layer kind adds files and
entries and edits none.

The window drives the program's train step as ``repro.launch.train.run``
builds it (``make_mesh``, ``train_in_shardings``, ``jit_train_step``
compiled ahead of time), with this benchmark's own loop around it: draw a
batch from the program's ``SyntheticLM``, ``device_put`` it to the batch
sharding, run the step, fetch the loss.  Each of the four calls is a
``bench.*`` span in the profiler trace.
"""
from __future__ import annotations

import gc
import gzip
import importlib.util
import json
import resource
import shutil
import sys
import time
from dataclasses import dataclass, is_dataclass
from pathlib import Path
from typing import (Callable, Dict, List, Optional, Sequence, get_args,
                    get_type_hints)

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import blocks, compare, flops, trace, traffic, weights
from chipbench.reference import Reference

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
OUT_DIR = ROOT / ".bench_out"
CHECKED_STEPS = 3


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: Path = BENCH_DIR


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT,
              bench_dir: Path = BENCH_DIR) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    mix = traffic.check_mix(json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()))
    limits_file = bench_dir / "limits" / f"{name}.json"
    limits = (json.loads(limits_file.read_text())["limits"]
              if limits_file.exists() else {})      # calibrate.py sets them
    return Cell(name=name, chips=w["chips"], config=config, mix=mix,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)],
                bench_dir=bench_dir)


def load_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """``read`` of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    mod_name = "chipbench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Context:
    """What the readers read."""
    step_s: List[float]
    window_s: float
    setup_s: float
    flops_per_step: int
    chips: int
    device_kind: str
    trace: Optional[dict] = None


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the registry's
    entry for ``arch`` with every key of ``model`` that names a field of
    ``ModelConfig`` (a dict becomes the field's dataclass, a list a tuple),
    and the reference's ``pattern``.  Keys that name no field, such as
    ``norm_eps`` (fixed at 1e-6 in the program), only the reference reads."""
    from repro.configs import get_config
    from repro.models.config import ModelConfig
    hints = get_type_hints(ModelConfig)
    kw = {"pattern": blocks.pattern(config["model"])}
    for key, value in config["model"].items():
        if key not in hints:
            continue
        if isinstance(value, dict):
            cls = next(t for t in (hints[key], *get_args(hints[key]))
                       if is_dataclass(t))
            value = cls(**value)
        kw[key] = tuple(value) if isinstance(value, list) else value
    return get_config(config["arch"]).replace(**kw)


class Program:
    """The system under test: the train step, compiled ahead of time on a
    (data, model) mesh over ``devices``, as ``launch/train.run`` builds
    it."""

    def __init__(self, config: dict, mix: dict, devices: Sequence):
        from repro.configs.shapes import train_batch_specs
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import jit_train_step, train_in_shardings
        from repro.models import param_shapes
        from repro.optim import make_optimizer

        m, hp = config["model"], config["optimizer"]
        want = weights.flatten(weights.shapes(m))    # refuses unknown kinds
        self.cfg = model_config(config)
        self.opt = make_optimizer(
            hp["name"], lr=hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
            weight_decay=hp["weight_decay"])
        self.mesh = make_mesh(list(devices))
        specs = train_batch_specs(self.cfg, mix["batch"], mix["seq_len"])
        in_sh, pshapes, oshapes = train_in_shardings(self.cfg, self.opt,
                                                     specs, self.mesh)
        self.p_sh, self.o_sh, self.b_sh = in_sh
        have = weights.flatten(param_shapes(self.cfg))
        if {p: x.shape for p, x in want.items()} != \
                {p: x.shape for p, x in have.items()}:
            raise ValueError("the program's parameter layout is not the one "
                             "weights.py makes")
        self.step = jit_train_step(self.cfg, self.opt, in_sh,
                                   self.mesh).lower(pshapes, oshapes,
                                                    specs).compile()
        self.init = jax.jit(lambda k: weights.init(k, m),
                            out_shardings=self.p_sh)
        self.opt_init = jax.jit(self.opt.init, out_shardings=self.o_sh)
        self.mu_norms = jax.jit(lambda o: compare.leaf_norms(o["mu"]))
        self.delta_norms = jax.jit(
            lambda p, k: compare.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, p, weights.init(k, m))))


def _one_step(prog: Program, data, params, opt_state, marks: list):
    """One step of the loop; ``marks`` gets the host clock at the end of
    each of its four calls."""
    with TraceAnnotation("bench.draw"):
        batch = data.next_batch()
    marks.append(time.perf_counter())
    with TraceAnnotation("bench.device_put"):
        batch = jax.device_put(batch, prog.b_sh)
    marks.append(time.perf_counter())
    with TraceAnnotation("bench.step"):
        params, opt_state, metrics = prog.step(params, opt_state, batch)
    marks.append(time.perf_counter())
    with TraceAnnotation("bench.fetch_loss"):
        loss = float(metrics["loss"])
    marks.append(time.perf_counter())
    return params, opt_state, loss, batch


class CompileCounter:
    """Counts backend compiles (JAX's monitoring events) while on."""

    def __init__(self):
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def program_first_steps(prog: Program, data, key, b1: float):
    """Set-up: weights and AdamW state from the seed, then the first
    ``CHECKED_STEPS`` steps through the window's own call and ``data``
    (``traffic.source``), with the readings the comparison needs.  Returns
    (params, opt_state, the readings, the rows fed)."""
    params = prog.init(key)
    opt_state = prog.opt_init(params)
    losses, fed, grad_norms = [], [], None
    for n in range(CHECKED_STEPS):
        params, opt_state, loss, batch = _one_step(prog, data, params,
                                                   opt_state, [])
        losses.append(loss)
        fed.append((np.asarray(batch["tokens"]), np.asarray(batch["labels"])))
        if n == 0:
            grad_norms = np.asarray(prog.mu_norms(opt_state)) / (1 - b1)
    delta = np.asarray(prog.delta_norms(params, key))
    return params, opt_state, {"losses": losses, "grad_norms": grad_norms,
                               "delta_norms": delta}, fed


class StepLog:
    """Where the window's slow steps went: for each step the host time of
    its four calls, the CPU time of the whole process, its major page
    faults, and the garbage collector's pauses.  Read on the host around
    each step; it changes nothing the step does."""

    CALLS = ("draw", "device_put", "step", "fetch_loss")

    def __init__(self):
        self.rows: List[dict] = []
        self._gc_s, self._gc_t = 0.0, None
        gc.callbacks.append(self._gc)
        self._last = self._sample()

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            self._gc_s += time.perf_counter() - self._gc_t
            self._gc_t = None

    def _sample(self):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return (ru.ru_utime + ru.ru_stime, ru.ru_majflt, self._gc_s)

    def add(self, marks: List[float]) -> None:
        now = self._sample()
        row = {c: b - a for c, a, b in zip(self.CALLS, marks, marks[1:])}
        row.update(zip(("cpu", "major_faults", "gc"),
                       (b - a for a, b in zip(self._last, now))))
        self.rows.append(row)
        self._last = now

    def close(self) -> None:
        gc.callbacks.remove(self._gc)

    def report(self, worst: int = 3) -> str:
        total = {k: sum(r[k] for r in self.rows) for k in
                 ("cpu", "major_faults", "gc")}
        slow = sorted(range(len(self.rows)),
                      key=lambda i: -sum(self.rows[i][c] for c in self.CALLS))
        lines = [f"window steps {len(self.rows)}; whole window: " + ", ".join(
            f"{k} {v:.4g}" for k, v in total.items())]
        for i in slow[:worst]:
            lines.append(f"slow step {i}: " + ", ".join(
                f"{k} {v:.4g}" for k, v in self.rows[i].items()))
        return "\n".join(lines)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(cell: Cell, devices: Sequence, seed: int, seconds: float,
             traced: bool, t0: float, log=print) -> dict:
    """One run; returns the result line's dict.  ``t0`` is the process's
    start on ``time.perf_counter``'s clock."""
    if not cell.limits:
        raise ValueError(f"{cell.name} has no limits/{cell.name}.json")
    m, mix = cell.config["model"], cell.mix
    counter = CompileCounter()
    prog = Program(cell.config, mix, devices)
    key = weights.seed_key(seed)
    data = traffic.source(prog.cfg, mix, seed)
    params, opt_state, prog_read, fed = program_first_steps(
        prog, data, key, cell.config["optimizer"]["b1"])

    trace_dir = OUT_DIR / "trace" / cell.name
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    counter.on = True
    steps = StepLog()
    step_s, losses = [], []
    with TraceAnnotation(trace.WINDOW_SPAN):
        start = last = time.perf_counter()
        while last - start < seconds:
            marks = [last]
            params, opt_state, loss, _ = _one_step(prog, data, params,
                                                   opt_state, marks)
            steps.add(marks)
            now = marks[-1]
            step_s.append(now - last)
            losses.append(loss)
            last = now
    steps.close()
    counter.on = False
    if traced:
        jax.profiler.stop_trace()
    setup_s = start - t0
    window_s = last - start
    peak = memory_peak(devices)
    del params, opt_state, prog
    gc.collect()

    ref = Reference(m, cell.config["optimizer"], devices, mix["batch"]).run(
        key, fed)
    readings = compare.gaps(prog_read, ref)
    readings["repeated_rows"] = compare.repeated_rows(fed)
    readings["nonfinite_losses"] = int(np.sum(~np.isfinite(losses)))
    limits = dict(cell.limits, repeated_rows=0, nonfinite_losses=0)
    chk = compare.checks(readings, limits)
    log(f"program losses {prog_read['losses']}, reference {ref['losses']}; "
        f"compiles in the window: {counter.count}", file=sys.stderr)
    log(steps.report(), file=sys.stderr)

    kind = devices[0].device_kind
    ctx = Context(step_s=step_s, window_s=window_s, setup_s=setup_s,
                  flops_per_step=flops.train_flops_per_step(
                      m, mix["batch"], mix["seq_len"]),
                  chips=len(devices), device_kind=kind)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": compare.passed(chk), "attempted": len(losses),
              "failed": readings["nonfinite_losses"]}
    if traced:
        ctx.trace = trace.reduce_xspace(trace.find_xspace(trace_dir),
                                        [d.id for d in devices])
        with gzip.open(OUT_DIR / f"{cell.name}.trace.json.gz", "wt") as f:
            json.dump(ctx.trace, f)
        lo, hi = trace.window(ctx.trace)
        busy = trace.busy_ns(ctx.trace)
        device["busy_s"] = float(np.mean(list(busy.values()))) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        metrics = cell.per_layer
    else:
        metrics = cell.end_to_end
    result["metrics"] = {}
    for spec in metrics:
        value = load_reader(spec["name"], cell.bench_dir)(ctx)
        if value is not None:
            result["metrics"][spec["name"]] = {"value": value,
                                               "unit": spec["unit"]}
    result["device"] = device
    if traced:
        result["breakdown"] = trace.breakdown(ctx.trace)
    result["checks"] = chk
    return result
