"""optimizer_device_ms_per_step: device milliseconds a step in operations
whose HLO op_name puts them in the program's ``optimizer`` scope (the
optimizer's update of parameters and state): the union of their intervals
in the window over the window's bench.step count, averaged over the chips
(chipbench/scopes.py).  An update that XLA fuses into its weight's
gradient matmul counts with that matmul's layer."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx.trace, "optimizer")
