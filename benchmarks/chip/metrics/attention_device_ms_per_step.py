"""attention_device_ms_per_step: device milliseconds a step in operations
whose HLO op_name puts them in the program's ``attention`` scope (QKV and
output projections, RoPE, scores, softmax, PV), forward, recomputation and
backward: the union of their intervals in the window over the window's
bench.step count, averaged over the chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx.trace, "attention")
