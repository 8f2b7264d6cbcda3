"""Block kind ``local``: ``attn``'s block with the program's sliding
window, ``model.window`` keys: query q sees key k where q - window < k <= q
(``layers.causal_mask``)."""
from __future__ import annotations

from chipbench.blocks import attn

layout = attn.layout
matmul_params = attn.matmul_params


def apply(p, x, m, low):
    return attn.apply(p, x, m, low, window=m["window"])


def score_flops_per_token(m: dict, seq_len: int) -> int:
    """PaLM's count over the keys a query sees."""
    return attn.score_flops_per_token(m, min(seq_len, m["window"]))
