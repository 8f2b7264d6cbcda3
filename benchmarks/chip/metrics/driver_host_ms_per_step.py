"""driver_host_ms_per_step: host milliseconds per step in the loop's own
work, the bench.draw, bench.device_put and bench.step (dispatch) spans of
the trace; the wait for the loss (bench.fetch_loss) is left out, since the
device is working then."""
from chipbench import trace

SPANS = ("bench.draw", "bench.device_put", "bench.step")


def read(ctx):
    if ctx.trace is None:
        return None
    ns, steps = trace.span_ns_per_step(ctx.trace, SPANS)
    return ns * 1e-6 / steps if steps else None
