"""From a profiler trace to the numbers the per-layer readers take.

``reduce_xspace`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two things: the benchmark's own host spans (names starting with
``bench.``) and, for each device used, the intervals of its XLA operations.
Everything else here works on that reduced form, a plain dict that a test
can hold:

    {"host_spans": [[name, start_ns, end_ns], ...],
     "devices": {"0": [[op_name, start_ns, end_ns], ...], ...}}

Device busy time is the union of a device's operation intervals inside the
window (the ``bench.window`` span); idle time is the rest of the window.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")
# Operations that hold others (a scan's loop): their interval covers the
# operations inside it and any gaps between them, so they are left out.
CONTAINERS = {"while", "conditional", "call"}
FIRST_TYPE = re.compile(r"\(?(\w+\[[\d,]*\])")

Interval = Tuple[float, float]


def op_label(name: str) -> str:
    """'fusion.36: fusion f32[4096,49152]' from the HLO text of a device
    event, "%fusion.36 = (f32[4096,49152]{1,0:T(8,128)}, ...) fusion(...)":
    instruction name, opcode and first result type without its layout.  A
    name that is not HLO text is kept as it is."""
    if " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    depth, end = 0, len(rhs)
    for i, c in enumerate(rhs):     # the result type ends at a space
        if c in "([{":              # outside brackets; the opcode runs
            depth += 1              # from there to its "("
        elif c in ")]}":
            depth -= 1
        elif c == " " and depth == 0:
            end = i
            break
    op = rhs[end + 1:].split("(", 1)[0]
    first = FIRST_TYPE.match(rhs)
    return f"{lhs.lstrip('%')}: {op}" + (f" {first.group(1)}" if first else "")


def opcode(label: str) -> str:
    parts = label.split(" ")
    return parts[1] if len(parts) > 1 and parts[0].endswith(":") else label


def leaf_ops(ops):
    return [(n, s, e) for n, s, e in ops if opcode(n) not in CONTAINERS]


def is_collective(label: str) -> bool:
    return bool(COLLECTIVE.search(label.split(" ")[0] + " " + opcode(label)))


def find_xspace(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_xspace(path: Path, device_ids: Iterable[int]) -> dict:
    from jax.profiler import ProfileData
    keep = {int(i) for i in device_ids}
    prof = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in prof.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in keep:
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[op_label(e.name), e.start_ns,
                             e.start_ns + e.duration_ns]
                            for e in line.events]
            devices[m.group(1)] = sorted(ops, key=lambda o: o[1])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name, e.start_ns, e.start_ns + e.duration_ns]
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"host_spans": sorted(spans, key=lambda s: s[1]),
            "devices": devices}


def window(tr: dict) -> Interval:
    found = [(s, e) for n, s, e in tr["host_spans"] if n == WINDOW_SPAN]
    if len(found) != 1:
        raise ValueError(f"{len(found)} {WINDOW_SPAN} spans in the trace")
    return found[0]


def union(intervals: Iterable[Sequence[float]], lo: float,
          hi: float) -> List[Interval]:
    """Merged, sorted intervals, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    got = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            got += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return got


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_ns(tr: dict) -> Dict[str, float]:
    lo, hi = window(tr)
    return {d: total(union(((s, e) for _, s, e in leaf_ops(ops)), lo, hi))
            for d, ops in tr["devices"].items()}


def collective_exposed_ns(tr: dict) -> Dict[str, float]:
    """Per device: time in which a collective runs and no other operation
    does.  A device that ran no collective is left out."""
    lo, hi = window(tr)
    out = {}
    for d, ops in tr["devices"].items():
        ops = leaf_ops(ops)
        coll = union(((s, e) for n, s, e in ops if is_collective(n)), lo, hi)
        if not coll:
            continue
        other = union(((s, e) for n, s, e in ops if not is_collective(n)),
                      lo, hi)
        out[d] = total(coll) - overlap(coll, other)
    return out


def span_ns_per_step(tr: dict, names: Sequence[str]) -> Tuple[float, int]:
    """Host time in the spans ``names`` inside the window, and the number of
    ``bench.step`` spans there."""
    lo, hi = window(tr)
    inside = [(n, s, e) for n, s, e in tr["host_spans"]
              if s >= lo and e <= hi]
    steps = sum(1 for n, _, _ in inside if n == "bench.step")
    return sum(e - s for n, s, e in inside if n in names), steps


def breakdown(tr: dict, top: int = 10) -> dict:
    """The device operations that took most time, and the idle time by what
    the host was doing, both in seconds averaged over the devices."""
    lo, hi = window(tr)
    ndev = max(len(tr["devices"]), 1)
    op_time: Dict[str, float] = defaultdict(float)
    idle_by: Dict[str, float] = defaultdict(float)
    by_label: Dict[str, List[Interval]] = defaultdict(list)
    for n, s, e in tr["host_spans"]:
        if n != WINDOW_SPAN:
            by_label[n].append((s, e))
    labelled = {n: union(iv, lo, hi) for n, iv in by_label.items()}
    for ops in tr["devices"].values():
        ops = leaf_ops(ops)
        for n, s, e in ops:
            op_time[n] += (min(e, hi) - max(s, lo)) / ndev \
                if e > lo and s < hi else 0.0
        idle = gaps(union(((s, e) for _, s, e in ops), lo, hi), lo, hi)
        left = total(idle)
        for n, iv in labelled.items():
            got = overlap(idle, iv)
            idle_by[n] += got / ndev
            left -= got
        idle_by["outside bench spans"] += max(left, 0.0) / ndev

    def ranked(d):
        return [[n, v * 1e-9] for n, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top] if v > 0]

    return {"device_ops": ranked(op_time), "idle_gaps": ranked(idle_by)}
