"""Operations a training step needs, from the configuration's shapes, and
the chip's peaks by ``device_kind``.

Model FLOPs per token follow PaLM (Chowdhery et al. 2022, App. B):
6 x the parameters that enter a matrix product (the embedding is a gather
and does not count; the LM head does) plus 12 * L * d_attn * S for the
attention scores and their weighted sum, where d_attn = heads * head_dim
and S the sequence length.  Recomputed (rematerialised) work does not count.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def matmul_params(m: dict) -> int:
    d, h, kv, hd, f = (m["d_model"], m["n_heads"], m["n_kv"], m["head_dim"],
                       m["d_ff"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (3 if m["mlp"] in ("swiglu", "geglu") else 2) * d * f
    return m["n_layers"] * (attn + mlp) + d * m["vocab"]


def train_flops_per_token(m: dict, seq_len: int) -> int:
    return (6 * matmul_params(m)
            + 12 * m["n_layers"] * m["n_heads"] * m["head_dim"] * seq_len)


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
