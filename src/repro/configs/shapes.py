"""Assigned input shapes and ShapeDtypeStruct input specs per (arch, shape).

LM transformer shapes (seq_len x global_batch):
  train_4k     4,096 x 256   training            -> lowers train_step
  prefill_32k  32,768 x 32   inference-prefill   -> lowers prefill_step
  decode_32k   32,768 x 128  inference-decode    -> lowers serve_step
                              (one new token, KV cache of seq_len)
  long_500k    524,288 x 1   long-context decode -> serve_step;
                              ONLY for sub-quadratic archs (ssm/hybrid)

``input_specs`` returns stand-ins (weak-type-correct, shardable, no device
allocation) for everything the lowered step consumes besides params.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.models.config import ModelConfig


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    """(applicable, reason-if-not). long_500k needs sub-quadratic attention."""
    sp = SHAPES[shape]
    if sp.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("pure full-attention arch: 524k dense causal "
                       "attention at batch 1 is out of scope (per DESIGN.md)")
    return True, ""


def _stub_inputs(cfg: ModelConfig, batch: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Modality-frontend stubs (precomputed frame/patch embeddings)."""
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    dt = jnp.dtype(cfg.dtype)
    if cfg.encoder_layers:  # audio: conv-frontend frames
        out["frames"] = jax.ShapeDtypeStruct(
            (batch, cfg.encoder_len, cfg.d_model), dt)
    elif cfg.cross_len:     # vlm: patch embeddings
        out["enc_embed"] = jax.ShapeDtypeStruct(
            (batch, cfg.cross_len, cfg.d_model), dt)
    return out


def train_input_specs(cfg: ModelConfig, shape: str) -> Dict:
    sp = SHAPES[shape]
    return train_batch_specs(cfg, sp.global_batch, sp.seq_len)


def train_batch_specs(cfg: ModelConfig, b: int, s: int) -> Dict:
    """Stand-ins for a (b, s) training batch (what ``SyntheticLM`` emits)."""
    specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    specs.update(_stub_inputs(cfg, b))
    return specs


def prefill_input_specs(cfg: ModelConfig, shape: str) -> Dict:
    sp = SHAPES[shape]
    b, s = sp.global_batch, sp.seq_len
    specs = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    specs.update(_stub_inputs(cfg, b))
    return specs


def decode_input_specs(cfg: ModelConfig, shape: str) -> Dict:
    """token + decode-state stand-ins (KV cache of seq_len / rnn state)."""
    sp = SHAPES[shape]
    b, s = sp.global_batch, sp.seq_len
    state = transformer.decode_state_shapes(cfg, b, s)
    return {"token": jax.ShapeDtypeStruct((b,), jnp.int32), "state": state}


def input_specs(cfg: ModelConfig, shape: str) -> Dict:
    kind = SHAPES[shape].kind
    if kind == "train":
        return train_input_specs(cfg, shape)
    if kind == "prefill":
        return prefill_input_specs(cfg, shape)
    return decode_input_specs(cfg, shape)
