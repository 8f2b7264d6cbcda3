"""Trace reduction: busy union, idle share, collective exposure, spans per
step and the breakdown, on a hand-made trace with known answers and on a
recorded one from a chip run."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, trace

DATA = Path(__file__).resolve().parent / "data"


def hand_made():
    # window 0..100; two steps; device 0 busy 10..40 (two overlapping ops)
    # and 60..90 with a collective 85..95 of which 90..95 is exposed;
    # device 1 busy 20..30 only.
    spans = [["bench.window", 0, 100],
             ["bench.draw", 0, 4], ["bench.device_put", 4, 5],
             ["bench.step", 5, 8], ["bench.fetch_loss", 8, 50],
             ["bench.draw", 50, 54], ["bench.device_put", 54, 55],
             ["bench.step", 55, 58], ["bench.fetch_loss", 58, 100]]
    dev0 = [["fusion.1", 10, 30], ["convolution.2", 25, 40],
            ["fusion.1", 60, 90], ["all-reduce.3", 85, 95]]
    dev1 = [["fusion.1", 20, 30]]
    return {"host_spans": spans, "devices": {"0": dev0, "1": dev1}}


def test_union_and_overlap():
    assert trace.union([(5, 8), (1, 3), (2, 4), (9, 12)], 0, 10) == \
        [(1, 4), (5, 8), (9, 10)]
    assert trace.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert trace.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


def test_busy_idle_and_exposed_collectives():
    tr = hand_made()
    assert trace.window(tr) == (0, 100)
    assert trace.busy_ns(tr) == {"0": 65, "1": 10}
    assert trace.collective_exposed_ns(tr) == {"0": 5}
    assert trace.span_ns_per_step(
        tr, ("bench.draw", "bench.device_put", "bench.step")) == (16, 2)


def test_readers_on_the_hand_made_trace():
    ctx = SimpleNamespace(trace=hand_made(), step_s=[0.5, 0.5],
                          window_s=1.0, setup_s=7.5,
                          flops_per_step=197e12 / 100, chips=1,
                          device_kind="TPU v5 lite")
    read = {n: harness.load_reader(n) for n in (
        "device_idle_share", "driver_host_ms_per_step",
        "collective_exposed_ms_per_step", "step_mfu", "step_ms_p90",
        "setup_s")}
    assert read["device_idle_share"](ctx) == pytest.approx(62.5)
    assert read["driver_host_ms_per_step"](ctx) == pytest.approx(8e-6)
    assert read["collective_exposed_ms_per_step"](ctx) == pytest.approx(
        2.5e-6)
    assert read["step_mfu"](ctx) == pytest.approx(2.0)
    assert read["setup_s"](ctx) == 7.5
    assert read["step_ms_p90"](ctx) is None          # under 10 steps
    ctx.step_s = [0.1 * (i + 1) for i in range(20)]
    assert read["step_ms_p90"](ctx) == pytest.approx(1e3 * 1.89)
    ctx.trace = None
    assert read["device_idle_share"](ctx) is None


def test_breakdown_of_the_hand_made_trace():
    b = trace.breakdown(hand_made())
    ops = dict(b["device_ops"])
    assert ops["fusion.1"] == pytest.approx((20 + 30 + 10) / 2 * 1e-9)
    idle = dict(b["idle_gaps"])
    # device 0 idles 0..10, 40..60, 95..100; device 1 0..20, 30..100
    assert sum(idle.values()) == pytest.approx((35 + 90) / 2 * 1e-9)
    assert idle["bench.fetch_loss"] == pytest.approx(
        ((2 + 10 + 2 + 5) + (12 + 20 + 42)) / 2 * 1e-9)


def recorded():
    with gzip.open(DATA / "granite-8b-1l.train_4k.trace.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_chip_trace():
    """Three steps of granite-8b-1l.train_4k on a TPU v5e: the device idles
    4.0% of the window, most of it while the host draws the next batch."""
    tr = recorded()
    lo, hi = trace.window(tr)
    busy = trace.busy_ns(tr)
    assert list(busy) == ["0"] and busy["0"] == 780_114_391
    assert hi - lo == 813_000_606
    assert trace.span_ns_per_step(
        tr, ("bench.draw", "bench.device_put", "bench.step")) == \
        (31_154_300, 3)
    assert trace.collective_exposed_ns(tr) == {}       # one chip
    b = trace.breakdown(tr)
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0] == ["fusion.36: fusion f32[4096,49152]",
                                  pytest.approx(0.117351717)]
    idle = dict(b["idle_gaps"])
    assert sum(idle.values()) == pytest.approx((hi - lo - busy["0"]) * 1e-9)
    assert max(idle, key=idle.get) == "bench.draw"


def test_op_labels_from_hlo_text():
    assert trace.op_label(
        "%fusion.36 = (f32[4096,49152]{1,0:T(8,128)}, f32[4096]{0}) "
        "fusion(f32[49152,4096]{1,0:T(8,128)} %a), kind=kLoop") == \
        "fusion.36: fusion f32[4096,49152]"
    label = trace.op_label("%all-gather-start.3 = (f32[1024]{0}, f32[4096]"
                           "{0}) all-gather-start(f32[1024]{0} %p)")
    assert trace.opcode(label) == "all-gather-start"
    assert trace.is_collective(label)
    loop = trace.op_label("%while.2 = (s32[], s32[2,4097]{1,0}) while("
                          "(s32[]) %t), condition=%c")
    assert trace.opcode(loop) == "while"
    assert trace.leaf_ops([[loop, 0, 9], ["x", 1, 2]]) == [("x", 1, 2)]
    assert trace.op_label("dot_general.1") == "dot_general.1"
