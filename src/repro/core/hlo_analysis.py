"""Compiled-HLO analysis: collective bytes, roofline terms.

``cost_analysis()`` gives total FLOPs and HBM bytes but NOT collective
traffic; we parse the compiled HLO text and sum the output-shape bytes of
every collective op (all-gather / all-reduce / reduce-scatter / all-to-all
/ collective-permute), counting ops inside while-loop (scan) bodies once
per trip via the loop trip count when derivable, else once.

Roofline terms (per device), TPU v5e constants:
    compute    = HLO_FLOPs / (chips * 197e12 bf16 FLOP/s)
    memory     = HLO_bytes / (chips * 819e9 B/s HBM)
    collective = collective_bytes / (chips * 2 links * 50e9 B/s ICI)
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link (~2 usable links/chip on a
ICI_LINKS = 2                # 2D torus slice in each sharded direction)
DCN_BW = 25e9                # bytes/s per host across pods (aggregate est.)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over every array shape in an HLO type string (handles
    tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    count_by_kind: Dict[str, int] = field(default_factory=dict)
    ops: List[Tuple[str, str, int, int]] = field(default_factory=list)
    # (kind, op name, bytes, multiplier)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def _loop_trip_counts(hlo: str) -> Dict[str, int]:
    """Best-effort: map while-body computation names to trip counts.

    XLA annotates compiled while loops with known trip counts via
    backend_config or induction-variable comments; the robust signal
    available in text form is the constant bound in the while condition:
        %cond { ... compare(..., s32[] constant(N)), direction=LT }
    We scan each computation ending in a compare-with-constant and treat N
    as the trip count for the while that uses it.
    """
    trips: Dict[str, int] = {}
    # split into computations
    comp_re = re.compile(r"^(?:%?)([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*?{",
                         re.M)
    # find condition computations: name -> constant compared
    const_cmp = re.compile(
        r"compare\([^)]*\)\s*,?\s*direction=LT", re.S)
    # simpler: find 'constant(N)' within computations whose name contains
    # 'cond' and a compare direction=LT
    blocks = re.split(r"\n\n", hlo)
    for b in blocks:
        header = b.strip().splitlines()[0] if b.strip() else ""
        m = re.match(r"%?([\w\.\-]+)", header.strip())
        if not m:
            continue
        name = m.group(1)
        if "cond" not in name:
            continue
        if "direction=LT" in b or "direction=LE" in b:
            consts = re.findall(r"constant\((\d+)\)", b)
            if consts:
                trips[name] = max(int(c) for c in consts)
    # map while ops to their condition computations
    mapping: Dict[str, int] = {}
    for m in re.finditer(
            r"while\([^)]*\),\s*condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)",
            hlo):
        cond, body = m.group(1), m.group(2)
        if cond in trips:
            mapping[body] = trips[cond]
    return mapping


def parse_collectives(hlo: str) -> CollectiveStats:
    stats = CollectiveStats()
    body_trips = _loop_trip_counts(hlo)
    # figure out which computation each line belongs to
    current_comp = ""
    mult = 1
    for line in hlo.splitlines():
        hdr = re.match(r"\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->",
                       line)
        if hdr and "{" in line:
            current_comp = hdr.group(1)
            mult = body_trips.get(current_comp, 1)
            continue
        for kind in _COLLECTIVES:
            if f" {kind}(" not in line and f" {kind}-start(" not in line:
                continue
            # e.g.  %ar = bf16[64,2048]{1,0} all-reduce(%x), ...
            m = re.search(
                rf"%?([\w\.\-]+)\s*=\s*(.*?)\s{kind}(?:-start)?\(", line)
            if m is None:
                continue
            name, type_str = m.group(1), m.group(2)
            out_bytes = _shape_bytes(type_str)
            if out_bytes == 0:
                continue
            n = _group_size(line)
            wire = _wire_bytes(kind, out_bytes, n)
            stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0) \
                + wire * mult
            stats.count_by_kind[kind] = stats.count_by_kind.get(kind, 0) \
                + mult
            stats.ops.append((kind, name, wire, mult))
            break
    return stats


def _group_size(line: str) -> int:
    """Participants per replica group (explicit or iota form)."""
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        ids = [s for s in m.group(1).split(",") if s.strip()]
        return max(len(ids), 1)
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[", line)
    if m:
        return max(int(m.group(2)), 1)
    return 1


def _wire_bytes(kind: str, out_bytes: int, n: int) -> int:
    """Per-device ICI wire traffic for one collective, ring algorithms.

    HLO shapes in the partitioned module are PER-DEVICE; ``out_bytes`` is
    the op's local output size.  Ring traffic per device:
      all-reduce       2 * (n-1)/n * local         (local == out)
      all-gather       (n-1)/n * gathered          (gathered == out)
      reduce-scatter   (n-1)/n * unscattered = (n-1) * out
      all-to-all       (n-1)/n * out
      collective-permute  out
    """
    if n <= 1:
        return out_bytes if kind == "collective-permute" else 0
    f = (n - 1) / n
    if kind == "all-reduce":
        return int(2 * f * out_bytes)
    if kind == "all-gather":
        return int(f * out_bytes)
    if kind == "reduce-scatter":
        return int((n - 1) * out_bytes)
    if kind == "all-to-all":
        return int(f * out_bytes)
    return out_bytes  # collective-permute


@dataclass
class RooflineTerms:
    """All byte/FLOP quantities are PER DEVICE (the compiled partitioned
    module's shapes are local); ``chips`` is used only for MFU/global
    throughput reporting."""

    hlo_flops: float             # per-device FLOPs of one step
    hlo_bytes: float             # per-device HBM bytes of one step
    collective_bytes: float      # per-device ICI wire bytes of one step
    chips: int
    model_flops: float = 0.0     # GLOBAL useful model FLOPs of one step

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (ICI_LINKS * ICI_BW)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        """Perfect-overlap bound: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def step_time_serial(self) -> float:
        """No-overlap bound: sum of the three terms."""
        return self.t_compute + self.t_memory + self.t_collective

    @property
    def useful_flops_ratio(self) -> float:
        total = self.hlo_flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the perfect-overlap bound."""
        t = self.step_time_lower_bound
        if t <= 0:
            return 0.0
        return self.model_flops / (t * self.chips * PEAK_FLOPS)

    def as_dict(self) -> Dict:
        return {
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes, "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "step_time_lower_bound_s": self.step_time_lower_bound,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu_bound,
        }


def model_flops_train(cfg, tokens: int) -> float:
    """6·N_active·D (dense backward included); MoE counts active params."""
    from repro.models.transformer import active_param_count
    return 6.0 * active_param_count(cfg) * tokens


def model_flops_decode(cfg, tokens: int, kv_len: int) -> float:
    """2·N_active per token plus attention reads over the KV cache."""
    from repro.models.transformer import active_param_count
    base = 2.0 * active_param_count(cfg) * tokens
    from repro.models.config import WINDOW_KINDS
    n_attn = sum(1 for k in (cfg.pattern * cfg.n_groups +
                             cfg.tail_pattern)
                 if k in ("attn", "moe", "encdec"))
    n_local = sum(1 for k in (cfg.pattern * cfg.n_groups +
                              cfg.tail_pattern) if k in WINDOW_KINDS)
    attn = 2.0 * 2.0 * cfg.n_heads * cfg.head_dim * (
        n_attn * kv_len + n_local * min(kv_len, cfg.window or kv_len))
    return base + attn * tokens
