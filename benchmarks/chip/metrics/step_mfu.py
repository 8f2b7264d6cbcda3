"""step_mfu: model FLOPs of the window's steps (chipbench/flops.py: 6 x
matmul params + 12 L d_attn S per token, nothing recomputed) over window
seconds x chips x the bf16 peak of the device kind, in %."""
from chipbench import flops


def read(ctx):
    if not ctx.step_s:
        return None
    peak = flops.peaks(ctx.device_kind)["bf16_flops_per_s"] * ctx.chips * ctx.window_s
    return 100.0 * ctx.flops_per_step * len(ctx.step_s) / peak
