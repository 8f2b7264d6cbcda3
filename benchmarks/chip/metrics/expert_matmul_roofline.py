"""expert_matmul_roofline: the grouped products of the held experts
against the chip's roofline, in %: the least time their work could take,
the larger of FLOPs over the bf16 peak and HBM bytes over the HBM peak
(``peaks.json``), over their device time in the window.

Work: ``expert_products`` of each MoE layer's block file
(``chipbench/blocks/<kind>.py``), from the cell's shapes at the expected
number of rows on the held experts; forward, its recomputation and both
gradients.  Time: the union of the ops of the grouped-matmul kernels
(megablox ``gmm`` and ``tgmm``), found by instruction name (``gmm.3:
custom-call``) or by a ``jit(gmm)``/``jit(tgmm)`` component of their
``op_name``; over the window's bench.step count, averaged over the chips.
A share of the kernels' roofline, not of the whole step.  Without a trace,
an MoE layer or a kernel op it reads nothing.

The cell's model and shapes come from ``--workload`` on the command line
of ``run.py``, the process that calls the readers."""
import re
import sys

from chipbench import blocks, flops, harness, scopes, trace

KERNELS = ("gmm", "tgmm")
INSTRUCTION = re.compile(r"(%s)(\.\d+)?:" % "|".join(KERNELS))


def _in_kernel(op_name: str) -> bool:
    for comp in op_name.split("/"):
        while (m := scopes.WRAPPED.fullmatch(comp)):
            comp = m.group(1)
        if comp in KERNELS:
            return True
    return False


def kernel_ns(tr: dict) -> float:
    """Device ns in the window in the kernels' ops, mean over the
    devices."""
    lo, hi = trace.window(tr)
    names = tr.get("op_names") or {}
    got = 0.0
    for d, ops in tr["devices"].items():
        ops_names = names.get(d) or [""] * len(ops)
        own = [(s, e) for (n, s, e), o in zip(ops, ops_names)
               if trace.opcode(n) not in trace.CONTAINERS
               and (INSTRUCTION.match(n) or _in_kernel(o))]
        got += trace.total(trace.union(own, lo, hi))
    return got / max(len(tr["devices"]), 1)


def work(m: dict, batch: int, seq_len: int):
    """(FLOPs, bytes) of a step's grouped products, over every layer."""
    fl = by = 0.0
    for kind in blocks.kinds(m):
        count = getattr(blocks.load(kind), "expert_products", None)
        if count is not None:
            f, b = count(m, batch, seq_len)
            fl, by = fl + f, by + b
    return fl, by


def share(tr: dict, m: dict, batch: int, seq_len: int, peak: dict):
    steps = trace.span_ns_per_step(tr, ())[1]
    fl, by = work(m, batch, seq_len)
    if not steps or not fl:
        return None
    scopes.op_names(tr)
    ns = kernel_ns(tr) / steps
    if not ns:
        return None
    least = max(fl / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)


def _workload():
    args = sys.argv
    for i, a in enumerate(args):
        if a == "--workload" and i + 1 < len(args):
            return args[i + 1]
        if a.startswith("--workload="):
            return a.split("=", 1)[1]
    return None


def read(ctx):
    name = _workload()
    if ctx.trace is None or name is None:
        return None
    cell = harness.load_cell(name)
    return share(ctx.trace, cell.config["model"], cell.mix["batch"],
                 cell.mix["seq_len"], flops.peaks(ctx.device_kind))
