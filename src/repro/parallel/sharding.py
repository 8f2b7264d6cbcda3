"""Logical-axis sharding: one model code path, any mesh.

Models annotate activations with *logical* names (``shard(x, "act_ff")``);
parameters are matched by pytree path. A :class:`ShardingRules` object maps
logical names to mesh axes. Outside a mesh context every annotation is a
no-op, so the same model runs on one CPU device.

Parallelism forms expressed through the rules (DP / FSDP / TP / EP / SP):
  * batch          -> ("pod", "data")      data parallelism (+ pod DP)
  * d_ff / heads   -> "model"              tensor parallelism
  * experts        -> "model"              expert parallelism
  * sequence       -> "model"/"data"       sequence/context parallelism
  * fsdp           -> "data"               parameter/optimizer sharding
"""
from __future__ import annotations

import re
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_ctx = threading.local()


# ---------------------------------------------------------------------------
# Activation annotations
# ---------------------------------------------------------------------------

# logical activation name -> PartitionSpec builder (axes names resolved late)
# Conventions: B=batch, S=sequence, H=heads, K=kv-heads, D=head_dim, F=d_ff,
# E=experts, M=d_model.
_ACT_SPECS: Dict[str, Tuple[Optional[str], ...]] = {
    # (B, S, F)
    "act_ff": ("batch", None, "tp"),
    # (B, S, H, D)
    "act_heads": ("batch", None, "tp", None),
    # (B, S, K, D): kv heads may be fewer than the tp degree; _shard_kv
    # picks the head-sharded variant only when K % tp == 0.
    "act_kv": ("batch", None, None, None),
    "act_kv_heads": ("batch", None, "tp", None),
    # (B, S, H, D) q for odd-head archs: sequence-parallel attention
    "act_heads_seq": ("batch", "sp", None, None),
    # (B, S, M) residual stream, sequence-sharded between blocks (SP)
    "act_seq": ("batch", "sp", None),
    # (B, S, M) residual stream, replicated sequence
    "act_btd": ("batch", None, None),
    # (B, S, V) logits
    "logits": ("batch", None, "tp"),
    # (B, S, K, D) decode KV cache: batch over data, cache seq over model
    # (flash-decoding style partial softmax handled by SPMD partitioner)
    "kv_cache": ("batch", "tp", None, None),
    # (B, S, R) recurrent width activations
    "act_rnn": ("batch", None, "tp"),
    # (n_slots, B, R) recurrent state
    "rnn_state": (None, "batch", "tp"),
}


@dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis roles to (tuples of) mesh axis names."""

    batch: Tuple[str, ...] = ("pod", "data")   # DP over these axes
    tp: Tuple[str, ...] = ("model",)           # tensor/expert parallel axis
    sp: Tuple[str, ...] = ("model",)           # sequence-parallel axis
    fsdp: Tuple[str, ...] = ("data",)          # parameter sharding axis

    def resolve(self, role: Optional[str],
                mesh: Mesh) -> Optional[Tuple[str, ...]]:
        if role is None:
            return None
        axes = tuple(a for a in getattr(self, role) if a in mesh.axis_names)
        return axes or None


@dataclass
class MeshContext:
    mesh: Mesh
    rules: ShardingRules = field(default_factory=ShardingRules)


def use_mesh(mesh: Optional[Mesh], rules: Optional[ShardingRules] = None):
    """Context manager enabling sharding annotations (None disables)."""

    class _Ctx:
        def __enter__(self):
            _ctx.current = MeshContext(mesh, rules or ShardingRules()) \
                if mesh is not None else None
            return self

        def __exit__(self, *a):
            _ctx.current = None

    return _Ctx()


def current_mesh() -> Optional[MeshContext]:
    return getattr(_ctx, "current", None)


def _spec_for(name: str, ndim: int, mc: MeshContext) -> Optional[P]:
    roles = _ACT_SPECS.get(name)
    if roles is None or len(roles) != ndim:
        return None
    parts = [mc.rules.resolve(r, mc.mesh) for r in roles]
    return P(*parts)


def role_size(role: str) -> int:
    """Mesh extent of a logical role (1 when no mesh context active)."""
    mc = current_mesh()
    if mc is None:
        return 1
    axes = mc.rules.resolve(role, mc.mesh)
    if not axes:
        return 1
    n = 1
    for a in axes:
        n *= mc.mesh.shape[a]
    return n


def shard(x, name: str):
    """Annotate activation ``x`` with the logical sharding ``name``."""
    mc = current_mesh()
    if mc is None:
        return x
    spec = _spec_for(name, getattr(x, "ndim", 0), mc)
    if spec is None:
        return x
    # only constrain if every sharded dim divides evenly
    for dim, part in zip(x.shape, spec):
        if part is None:
            continue
        n = int(np.prod([mc.mesh.shape[a] for a in
                         (part if isinstance(part, tuple) else (part,))]))
        if dim % n != 0:
            return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mc.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter shardings (by pytree path)
# ---------------------------------------------------------------------------

# Patterns are matched against '/'-joined pytree key paths. First match wins.
# Axis tuples use role names resolved through ShardingRules.
# None = replicated dim.
_PARAM_RULES: List[Tuple[str, Tuple[Optional[str], ...]]] = [
    # embeddings (V, M): vocab over tp, model dim over fsdp
    (r"(^|/)embed$", ("tp", "fsdp")),
    (r"(^|/)lm_head$", ("fsdp", "tp")),
    (r"(^|/)pos_embed$", (None, None)),
    # attention (stacked: leading scan dim handled dynamically)
    (r"wq$", ("fsdp", "tp", None)),    # (M, H, D)
    (r"wk$", ("fsdp", None, None)),    # (M, K, D) kv heads usually < tp
    (r"wv$", ("fsdp", None, None)),
    (r"wo$", ("tp", None, "fsdp")),    # (H, D, M)
    # xLSTM projections
    (r"lstm_wqkv$", ("fsdp", None, "tp", None)),  # (M, 3, H, D)
    (r"lstm_wx$", ("fsdp", None, "tp", None)),    # (M, 4, H, D)
    (r"lstm_wh$", ("tp", None, None, None)),      # (H, D, 4, D)
    (r"lstm_w(if|og)$", ("fsdp", None)),          # (M, ...) projections
    # MLP (M, F) / (F, M): F over tp, M over fsdp
    (r"(mlp|dense_ff)/wi$", ("fsdp", "tp")),
    (r"(mlp|dense_ff)/wg$", ("fsdp", "tp")),
    (r"(mlp|dense_ff)/wo$", ("tp", "fsdp")),
    # MoE experts (E, M, F): experts over tp, F over fsdp
    (r"experts/wi$", ("tp", None, "fsdp")),
    (r"experts/wg$", ("tp", None, "fsdp")),
    (r"experts/wo$", ("tp", "fsdp", None)),
    (r"router/w$", (None, None)),
    # shared experts: like dense MLP
    (r"shared/wi$", ("fsdp", "tp")),
    (r"shared/wg$", ("fsdp", "tp")),
    (r"shared/wo$", ("tp", "fsdp")),
    # RG-LRU / recurrent blocks (M, R) projections: R over tp
    (r"(rg|rnn|lstm)[^/]*/w[a-z]*$", (None, "tp")),
    # norms / gates / scalars: replicated
    (r".*", None),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_spec(path: str, ndim: int, mesh: Mesh,
               rules: ShardingRules) -> P:
    for pat, roles in _PARAM_RULES:
        if re.search(pat, path):
            if roles is None:
                return P()
            roles = tuple(roles)
            if len(roles) < ndim:  # stacked leading scan dims -> replicated
                roles = (None,) * (ndim - len(roles)) + roles
            elif len(roles) > ndim:
                return P()
            parts = [rules.resolve(r, mesh) for r in roles]
            return P(*parts)
    return P()


def params_shardings(params, mesh: Mesh,
                     rules: Optional[ShardingRules] = None):
    """NamedSharding pytree for a parameter pytree, with divisibility guard."""
    rules = rules or ShardingRules()

    def leaf(path, x):
        spec = param_spec(_path_str(path), x.ndim, mesh, rules)
        parts = list(spec)
        ok_parts = []
        for dim, part in zip(x.shape, parts):
            if part is None:
                ok_parts.append(None)
                continue
            axes = part if isinstance(part, tuple) else (part,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            ok_parts.append(part if dim % n == 0 else None)
        ok_parts += [None] * (x.ndim - len(ok_parts))
        return NamedSharding(mesh, P(*ok_parts))

    return jax.tree_util.tree_map_with_path(leaf, params)


# Decode-state leaf rules (matched by trailing path component). Leading
# ``n_slots`` scan dims are padded with None automatically.
_STATE_RULES: List[Tuple[str, Tuple[Optional[str], ...]]] = [
    (r"(^|/)x?k$", ("batch", "tp", None, None)),   # KV cache (B,S,K,D)
    (r"(^|/)x?v$", ("batch", "tp", None, None)),
    (r"(^|/)h$", ("batch", "tp")),                 # rnn state (B,R)
    (r"(^|/)conv$", ("batch", None, "tp")),        # (B,W-1,R)
    (r"(^|/)C$", ("batch", "tp", None, None)),     # mLSTM (B,H,hd,hd)
    (r"(^|/)[cnm]$", ("batch", "tp", None)),       # sLSTM (B,H,hd) / (B,H)
    (r".*", None),
]


def decode_state_shardings(state, mesh: Mesh,
                           rules: Optional[ShardingRules] = None):
    """NamedSharding pytree for a decode state (KV caches / rnn state)."""
    rules = rules or ShardingRules()

    def leaf(path, x):
        pstr = _path_str(path)
        for pat, roles in _STATE_RULES:
            if re.search(pat, pstr):
                if roles is None or x.ndim == 0:
                    return NamedSharding(mesh, P())
                r = tuple(roles)[: x.ndim]
                if len(r) < x.ndim:   # stacked scan dim(s) on the left
                    r = (None,) * (x.ndim - len(r)) + r
                parts = [rules.resolve(role, mesh) for role in r]
                ok = []
                for dim, part in zip(x.shape, parts):
                    if part is None:
                        ok.append(None)
                        continue
                    axes = part if isinstance(part, tuple) else (part,)
                    n = int(np.prod([mesh.shape[a] for a in axes]))
                    ok.append(part if dim % n == 0 else None)
                return NamedSharding(mesh, P(*ok))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf, state)


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, ndim: int = 2,
                   rules: Optional[ShardingRules] = None):
    """Inputs (B, S, ...) sharded on batch only."""
    rules = rules or ShardingRules()
    axes = rules.resolve("batch", mesh)
    return NamedSharding(mesh, P(axes, *([None] * (ndim - 1))))
