"""device_idle_share: 1 - (union of the device's operation intervals in the
window / the window), from the profiler trace, averaged over the chips, in
%."""
from chipbench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    lo, hi = trace.window(ctx.trace)
    busy = trace.busy_ns(ctx.trace).values()
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
