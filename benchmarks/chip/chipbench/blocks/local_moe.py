"""Block kind ``local_moe``: ``moe``'s block with the program's sliding
window, ``model.window`` keys (query q sees key k where q - window < k <= q,
``layers.causal_mask``), and plain RoPE: YaRN scales only the full-sequence
layers."""
from __future__ import annotations

from chipbench.blocks import attn, moe

layout = moe.layout
matmul_params = moe.matmul_params
expert_products = moe.expert_products


def apply(p, x, m, low):
    return moe.apply(p, x, m, low, window=m["window"])


def score_flops_per_token(m: dict, seq_len: int) -> int:
    """PaLM's count over the keys a query sees."""
    return attn.score_flops_per_token(m, min(seq_len, m["window"]))
