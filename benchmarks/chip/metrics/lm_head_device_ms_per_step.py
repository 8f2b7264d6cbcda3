"""lm_head_device_ms_per_step: device milliseconds a step in operations
whose HLO op_name puts them in the program's ``lm_head`` scope (the head
matmul over the vocabulary, softcap and vocabulary mask, the f32 logits,
logsumexp and cross-entropy), forward and backward: the union of their
intervals in the window over the window's bench.step count, averaged over
the chips (chipbench/scopes.py)."""
from chipbench import scopes


def read(ctx):
    return scopes.ms_per_step(ctx.trace, "lm_head")
