"""Operations a training step needs, from the configuration's shapes, and
the chip's peaks by ``device_kind``.

Model FLOPs per token follow PaLM (Chowdhery et al. 2022, App. B):
6 x the parameters that enter a matrix product (the embedding is a gather
and does not count; the LM head does) plus, for each layer, 12 * d_attn * S
for the attention scores and their weighted sum, where d_attn = heads *
head_dim and S the keys a query sees: the sequence length, or the window of
a ``local`` layer.  Each layer kind's ``blocks/<kind>.py`` counts its own
part.  Recomputed (rematerialised) work does not count.
"""
from __future__ import annotations

import json
from pathlib import Path

from chipbench import blocks

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def matmul_params(m: dict) -> int:
    return (sum(blocks.load(k).matmul_params(m) for k in blocks.kinds(m))
            + m["d_model"] * m["vocab"])


def train_flops_per_token(m: dict, seq_len: int) -> int:
    return 6 * matmul_params(m) + sum(
        blocks.load(k).score_flops_per_token(m, seq_len)
        for k in blocks.kinds(m))


def train_flops_per_step(m: dict, batch: int, seq_len: int) -> int:
    return batch * seq_len * train_flops_per_token(m, seq_len)


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The row of ``peaks.json`` for ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}; known: {sorted(table)}")
    return table[device_kind]
