"""mlp_device_ms_per_step: device milliseconds a step in operations whose
HLO op_name puts them in the program's ``mlp`` scope, forward,
recomputation and backward: the union of their intervals in the window
over the window's bench.step count, averaged over the chips
(chipbench/scopes.py).  A weight-gradient matmul that XLA fuses with the
weight's AdamW update counts here."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx.trace, "mlp")
