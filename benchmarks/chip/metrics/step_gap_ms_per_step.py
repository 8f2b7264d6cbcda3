"""step_gap_ms_per_step: device milliseconds a step idle between the first
and the last operation of each train-step execution (ops under
jit(train_step)/), where no operation of any program runs: idle that the
program's own schedule leaves and the host did not cause.  Over the
window's bench.step count, averaged over the chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(ctx):
    return scopes.step_gap_ms_per_step(ctx.trace)
