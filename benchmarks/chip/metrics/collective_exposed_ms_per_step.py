"""collective_exposed_ms_per_step: device milliseconds per step in which an
all-gather, all-reduce, reduce-scatter, collective-permute or all-to-all
runs and no other operation does on that device, averaged over the chips
that ran one.  Nothing to read on one chip."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None:
        return None
    exposed = trace.collective_exposed_ns(ctx.trace)
    _, steps = trace.span_ns_per_step(ctx.trace, ())
    if not exposed or not steps:
        return None
    return sum(exposed.values()) / len(exposed) * 1e-6 / steps
