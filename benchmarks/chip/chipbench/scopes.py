"""Device time by layer, from the layer scope in each device op's HLO
``op_name``.

The program runs each layer kind under one ``jax.named_scope``
(``repro.models.layers.scoped``): ``embed``, ``norm``, ``attention``,
``mlp``, ``moe``, ``recurrent``, ``lm_head`` and ``optimizer``.  The name
reaches the ``op_name`` metadata of the layer's HLO instructions through
``jvp``, ``transpose`` and rematerialisation, as one whole component of the
path: ``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
mlp/...d,df->...f/dot_general``.  A fused op carries the ``op_name`` of one
of its instructions, so a bucket is exact only to the fusion.

The reduced trace (``trace.reduce_xspace``) gets one more key, each device's
``op_name`` list aligned with its ``devices`` list:

    {"op_names": {"0": [op_name, ...], ...}}

``op_names`` adds it from the run's ``.xplane.pb`` the first time a reader
asks for it.  A trace whose ops carry no layer scope (a program without the
scopes) reads nothing.
"""
from __future__ import annotations

import re
from bisect import bisect_left
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import trace

SCOPES = ("embed", "norm", "attention", "mlp", "moe", "recurrent",
          "lm_head", "optimizer")
TRAIN_STEP = "jit(train_step)/"
# The stat of an ``XLA Ops`` event's metadata that holds its ``op_name``.
OP_NAME_STAT = "tf_op"
WRAPPED = re.compile(r"[\w.-]+\((.*)\)")       # jvp(x), transpose(jvp(x))


def scope_of(op_name: str) -> Optional[str]:
    """The innermost layer scope among the whole components of
    ``op_name``, each with its ``jvp(...)``/``transpose(...)`` wrappers
    taken off; None outside every scope."""
    found = None
    for comp in op_name.split("/"):
        while (m := WRAPPED.fullmatch(comp)):
            comp = m.group(1)
        if comp in SCOPES:
            found = comp
    return found


def _varint(buf: bytes, pos: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        byte = buf[pos]
        pos += 1
        out |= (byte & 0x7F) << shift
        if byte < 0x80:
            return out, pos
        shift += 7


def _fields(buf: bytes, span: Tuple[int, int]):
    """(field number, value) of each field of the protobuf message in
    ``buf[span[0]:span[1]]``: an int for a scalar, a (start, end) span for
    a length-delimited field."""
    pos, end = span
    while pos < end:
        key, pos = _varint(buf, pos)
        wire = key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            n, pos = _varint(buf, pos)
            value, pos = (pos, pos + n), pos + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, pos = int.from_bytes(buf[pos:pos + n], "little"), pos + n
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode()


def _map_entries(buf: bytes, spans):
    """(key, value span) of each entry of a protobuf map field."""
    for span in spans:
        got = dict(_fields(buf, span))
        yield got.get(1, 0), got.get(2, (0, 0))


def _plane_ops(buf: bytes, plane: Tuple[int, int]):
    """Name of an ``XPlane`` and, for each event of its ``XLA Ops`` line in
    file order, (start ns, end ns, op label, op_name), with start and end
    as ``jax.profiler.ProfileData`` gives them."""
    name, lines, events_meta, stats_meta = "", [], [], []
    for f, v in _fields(buf, plane):   # XPlane: name 2, lines 3,
        if f == 2:                     # event_metadata 4, stat_metadata 5
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            events_meta.append(v)
        elif f == 5:
            stats_meta.append(v)
    if not trace.DEVICE_PLANE.match(name):
        return name, []
    stat_id = None                     # XStatMetadata: name 2
    for key, v in _map_entries(buf, stats_meta):
        got = dict(_fields(buf, v))
        if 2 in got and _text(buf, got[2]) == OP_NAME_STAT:
            stat_id = key
    meta = {}                          # XEventMetadata: name 2, stats 5
    for key, v in _map_entries(buf, events_meta):
        label, op = "", ""
        for f, w in _fields(buf, v):
            if f == 2:
                label = trace.op_label(_text(buf, w))
            elif f == 5:
                stat = dict(_fields(buf, w))   # XStat: metadata_id 1,
                if stat.get(1) == stat_id and 5 in stat:   # str_value 5
                    op = _text(buf, stat[5])
                    op = op.rpartition(":")[0] if ":" in op else op
        meta[key] = (label, op)
    rows = []
    for line in lines:                 # XLine: name 2, timestamp_ns 3,
        got = list(_fields(buf, line))  # events 4
        if not any(f == 2 and _text(buf, v) == trace.OPS_LINE
                   for f, v in got):
            continue
        t0 = next((v for f, v in got if f == 3), 0)
        for f, ev in got:              # XEvent: metadata_id 1,
            if f == 4:                 # offset_ps 2, duration_ps 3
                e = dict(_fields(buf, ev))
                start = t0 + e.get(2, 0) // 1000
                rows.append((start, start + e.get(3, 0) // 1000)
                            + meta.get(e.get(1, 0), ("", "")))
    return name, rows


def reduce_op_names(path: Path, tr: dict) -> Optional[Dict[str, List[str]]]:
    """Each device's ``op_name`` list from the ``.xplane.pb`` at ``path``,
    in the order of ``tr["devices"]``; None if the file's ops are not the
    ones ``tr`` holds.

    The ``op_name`` is the ``tf_op`` stat of each ``XLA Ops`` event's
    metadata ("<op_name>:<op type>"), which ``ProfileData`` does not
    expose, so the file is read here as the protobuf it is
    (``tsl/profiler/protobuf/xplane.proto``)."""
    buf = Path(path).read_bytes()
    out = {}
    for f, plane in _fields(buf, (0, len(buf))):     # XSpace: planes 1
        if f != 1:
            continue
        name, rows = _plane_ops(buf, plane)
        m = trace.DEVICE_PLANE.match(name)
        if not m or m.group(1) not in tr["devices"]:
            continue
        rows.sort(key=lambda r: r[0])      # stable, as reduce_xspace sorts
        if [[n, s, e] for s, e, n, _ in rows] != tr["devices"][m.group(1)]:
            return None
        out[m.group(1)] = [o for *_, o in rows]
    return out if set(out) == set(tr["devices"]) else None


def op_names(tr: dict, trace_root: Optional[Path] = None
             ) -> Optional[Dict[str, List[str]]]:
    """``tr["op_names"]``; where the reduced trace lacks it, read from the
    newest ``.xplane.pb`` under ``trace_root`` (the harness's trace
    directory) and kept in ``tr``, if that file is the one ``tr`` was
    reduced from."""
    if "op_names" not in tr:
        if trace_root is None:
            from chipbench.harness import OUT_DIR
            trace_root = OUT_DIR / "trace"
        found = sorted(Path(trace_root).rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        tr["op_names"] = reduce_op_names(found[-1], tr) if found else None
    return tr["op_names"]


def _steps(tr: dict) -> int:
    return trace.span_ns_per_step(tr, ())[1]


def _leaf_rows(tr: dict, d: str):
    """(op_name, start, end) of device ``d``'s ops that hold no others."""
    return [(o, s, e) for (n, s, e), o in zip(tr["devices"][d],
                                               tr["op_names"][d])
            if trace.opcode(n) not in trace.CONTAINERS]


def scope_ns(tr: dict) -> Optional[Dict[str, float]]:
    """Device ns in the window by innermost scope (None: outside every
    scope), mean over the devices: per scope, the union of its ops'
    intervals.  None where no op of the trace carries a scope."""
    names = op_names(tr)
    if names is None:
        return None
    lo, hi = trace.window(tr)
    scope = {o: scope_of(o) for v in names.values() for o in set(v)}
    out: Dict[Optional[str], float] = {}
    for d in tr["devices"]:
        by: Dict[Optional[str], list] = {}
        for o, s, e in _leaf_rows(tr, d):
            by.setdefault(scope[o], []).append((s, e))
        for k, iv in by.items():
            out[k] = out.get(k, 0.0) + trace.total(
                trace.union(iv, lo, hi)) / len(tr["devices"])
    return out if set(out) - {None} else None


def ms_per_step(tr: Optional[dict], scope: str) -> Optional[float]:
    """Device ms a step in ops of ``scope``; None without a trace, without
    scoped ops or without a step in the window."""
    if tr is None:
        return None
    got, steps = scope_ns(tr), _steps(tr)
    if got is None or not steps:
        return None
    return got.get(scope, 0.0) * 1e-6 / steps


def step_gaps(tr: dict) -> Optional[Dict[str, List[trace.Interval]]]:
    """Per device, the idle intervals inside each train-step execution:
    between the first and the last op whose ``op_name`` lies under
    ``jit(train_step)/`` among the ops that start between a ``bench.step``
    span's start and the end of the ``bench.fetch_loss`` after it (the
    loss fetch waits for the step to end).  Idle means no op of any
    program runs."""
    names = op_names(tr)
    if names is None:
        return None
    lo, hi = trace.window(tr)
    spans = [(n, s, e) for n, s, e in tr["host_spans"]
             if s >= lo and e <= hi]
    runs, start = [], None
    for n, s, e in spans:
        if n == "bench.step":
            start = s
        elif n == "bench.fetch_loss" and start is not None:
            runs.append((start, e))
            start = None
    out = {}
    for d in tr["devices"]:
        rows = _leaf_rows(tr, d)               # sorted by start
        starts = [s for _, s, _ in rows]
        gaps: List[trace.Interval] = []
        for a, b in runs:
            inside = rows[bisect_left(starts, a):bisect_left(starts, b)]
            own = [(s, e) for o, s, e in inside if o.startswith(TRAIN_STEP)]
            if not own:
                continue
            first, last = min(s for s, _ in own), max(e for _, e in own)
            busy = trace.union(((s, e) for _, s, e in inside), first, last)
            gaps += trace.gaps(busy, first, last)
        out[d] = gaps
    return out


def step_gap_ms_per_step(tr: Optional[dict]) -> Optional[float]:
    """Device ms a step idle inside train-step executions, mean over the
    devices."""
    if tr is None:
        return None
    gaps, steps = step_gaps(tr), _steps(tr)
    if not gaps or not steps:
        return None
    return sum(trace.total(g) for g in gaps.values()) / len(gaps) \
        * 1e-6 / steps
