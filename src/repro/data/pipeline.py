"""Deterministic synthetic data pipeline: shard-aware, checkpointable.

Produces LM batches (tokens/labels) plus modality stubs (frames / patch
embeddings) per the arch's input spec.  Every batch is a pure function of
(seed, step, shard), so (a) restarts resume bit-exactly from a checkpointed
``DataState`` and (b) elastic re-sharding (changing num_shards) keeps the
global batch sequence deterministic.

The synthetic LM distribution is a Zipf-like unigram stream with a
shifting-window Markov flavor — enough structure for loss to fall during
the examples' few-hundred-step runs, while requiring no disk datasets.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig


@dataclass
class DataState:
    seed: int
    step: int
    shard: int
    num_shards: int

    def as_dict(self) -> Dict[str, int]:
        return {"seed": self.seed, "step": self.step, "shard": self.shard,
                "num_shards": self.num_shards}

    @classmethod
    def from_dict(cls, d) -> "DataState":
        return cls(**{k: int(v) for k, v in d.items()})


class SyntheticLM:
    """Infinite deterministic token stream."""

    def __init__(self, cfg: ModelConfig, global_batch: int, seq_len: int,
                 seed: int = 0, shard: int = 0, num_shards: int = 1):
        if global_batch % num_shards:
            raise ValueError("global_batch must divide num_shards")
        self.cfg = cfg
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.state = DataState(seed=seed, step=0, shard=shard,
                               num_shards=num_shards)
        # Zipf-ish unigram over the vocab (stable across shards/steps),
        # kept as its CDF for inverse-CDF draws
        p = 1.0 / np.arange(1, cfg.vocab + 1, dtype=np.float64)
        self._cdf = jnp.asarray(np.cumsum(p / p.sum()), jnp.float32)

    @property
    def shard_batch(self) -> int:
        return self.global_batch // self.state.num_shards

    def _batch_key(self, step: int, shard: int):
        k = jax.random.PRNGKey(self.state.seed)
        k = jax.random.fold_in(k, step)
        return jax.random.fold_in(k, shard)

    def next_batch(self) -> Dict[str, jnp.ndarray]:
        st = self.state
        key = self._batch_key(st.step, st.shard)
        b, s = self.shard_batch, self.seq_len
        ks = jax.random.split(key, 3)
        # inverse-CDF draw: O(b*s) memory, where a categorical draw holds
        # (b, s, vocab) noise (6.4 GB at batch 8 x 4096, vocab 49152)
        u = jax.random.uniform(ks[0], (b, s + 1))
        stream = jnp.minimum(jnp.searchsorted(self._cdf, u, side="right"),
                             self.cfg.vocab - 1)
        # simple structure: every 2nd token repeats its predecessor mod V
        rep = jnp.roll(stream, 1, axis=1)
        mask = (jnp.arange(s + 1)[None, :] % 2).astype(bool)
        stream = jnp.where(mask, (rep + 1) % self.cfg.vocab, stream)
        batch = {"tokens": stream[:, :-1].astype(jnp.int32),
                 "labels": stream[:, 1:].astype(jnp.int32)}
        if self.cfg.encoder_layers:
            batch["frames"] = 0.1 * jax.random.normal(
                ks[1], (b, self.cfg.encoder_len, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        elif self.cfg.cross_len:
            batch["enc_embed"] = 0.1 * jax.random.normal(
                ks[2], (b, self.cfg.cross_len, self.cfg.d_model),
                jnp.dtype(self.cfg.dtype))
        self.state = DataState(st.seed, st.step + 1, st.shard,
                               st.num_shards)
        return batch

    # -- checkpoint integration ----------------------------------------------

    def state_dict(self) -> Dict[str, int]:
        return self.state.as_dict()

    def load_state_dict(self, d, shard: Optional[int] = None,
                        num_shards: Optional[int] = None) -> None:
        st = DataState.from_dict(d)
        if shard is not None:     # elastic re-shard on resume
            st = DataState(st.seed, st.step, shard,
                           num_shards or st.num_shards)
        self.state = st
