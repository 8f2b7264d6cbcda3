"""moe_device_ms_per_step: device milliseconds a step in operations whose
HLO op_name puts them in the program's ``moe`` scope (router, dispatch,
the held experts' grouped products, combine; the sub-scopes ``router``,
``dispatch``, ``experts`` and ``combine`` count here), forward,
recomputation and backward: the union of their intervals in the window
over the window's bench.step count, averaged over the chips
(chipbench/scopes.py).  A program without the scope reads nothing."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx.trace, "moe")
