"""step_ms_p90: the 90th percentile, over every step of the window, of the
host time from one step boundary to the next (batch draw to the loss on the
host), so stalls count."""
import statistics


def read(ctx):
    if len(ctx.step_s) < 10:
        return None
    return 1e3 * statistics.quantiles(ctx.step_s, n=10)[8]
