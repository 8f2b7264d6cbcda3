"""attention_kernel_device_ms_per_step: device milliseconds a step in the
fused attention kernels' own ops: the Pallas calls named
``flash_attention_fwd`` (forward and its recomputation),
``flash_attention_dq`` and ``flash_attention_dkv``.  An op is theirs when
its HLO instruction is named after one of them (``flash_attention_dq.3``),
or when one of them is a whole component of its ``op_name`` (each call is a
``jax.named_scope`` of that name).  The union of their intervals in the
window over the window's bench.step count, averaged over the chips.  A
traced program that does not run the kernels reads 0."""
import re

from chipbench import scopes, trace

KERNELS = ("flash_attention_fwd", "flash_attention_dq",
           "flash_attention_dkv")
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


def read(ctx):
    if ctx.trace is None:
        return None
    steps = trace.span_ns_per_step(ctx.trace, ())[1]
    if not steps:
        return None
    return kernel_ns(ctx.trace) * 1e-6 / steps
