"""moe_device_ms_per_step and expert_matmul_roofline on a hand-made trace
with known answers."""
import sys
from types import SimpleNamespace

import pytest

from chipbench import flops, harness

STEP = "jit(train_step)/"
MOE = STEP + "jvp()/while/body/closed_call/moe/"
BWD = STEP + "transpose(jvp())/while/body/closed_call/checkpoint/moe/"
CELL = "mellum2-12b-4l.train_4k_b8"
ROOF = "expert_matmul_roofline"


def hand_made():
    # window 0..1000 ns with two steps.  Device 0: router 10..20, a dispatch
    # gather 20..40, a forward gmm 40..100, the silu product 100..110 (in
    # experts, no kernel), a tgmm 200..300 found by its op_name alone, a
    # combine gather 300..320, an attention op 320..400; device 1: a gmm
    # 50..150 named by its instruction.
    spans = [["bench.window", 0, 1000], ["bench.step", 5, 8],
             ["bench.step", 500, 508]]
    dev0 = [
        ("fusion.1: fusion f32[64]", 10, 20, MOE + "router/dot_general"),
        ("fusion.2: fusion bf16[64,8]", 20, 40, MOE + "dispatch/gather"),
        ("gmm.3: custom-call bf16[64,8]", 40, 100,
         MOE + "experts/cond/branch_0_fun/jit(gmm)/pallas_call"),
        ("fusion.4: fusion bf16[64,8]", 100, 110, MOE + "experts/mul"),
        ("custom-call.5: custom-call bf16[4,8,8]", 200, 300,
         BWD + "experts/cond/branch_0_fun/jit(tgmm)/pallas_call"),
        ("fusion.6: fusion bf16[64,8]", 300, 320, BWD + "combine/gather"),
        ("fusion.7: fusion bf16[64,8]", 320, 400,
         MOE[:-4] + "attention/dot_general")]
    dev1 = [("gmm.8: custom-call bf16[64,8]", 50, 150,
             MOE + "experts/jit(gmm)/pallas_call")]
    devs = {"0": dev0, "1": dev1}
    return {"host_spans": spans,
            "devices": {d: [[n, s, e] for n, s, e, _ in v]
                        for d, v in devs.items()},
            "op_names": {d: [o for *_, o in v] for d, v in devs.items()}}


def ctx_of(tr):
    return SimpleNamespace(trace=tr, step_s=[0.5, 0.5], window_s=1.0,
                           setup_s=7.5, flops_per_step=1.0, chips=2,
                           device_kind="TPU v5 lite")


def test_moe_ms_per_step():
    got = harness.load_reader("moe_device_ms_per_step")(ctx_of(hand_made()))
    # device 0: 10..110, 200..320 = 220 ns; device 1: its gmm, 100 ns;
    # mean over the devices, over 2 steps; the attention op is left out
    assert got == pytest.approx((220 + 100) / 2 / 2 * 1e-6)


def test_roofline_of_the_hand_made_trace(monkeypatch):
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    reader = harness.load_reader(ROOF)
    cell = harness.load_cell(CELL)
    m = cell.config["model"]
    rows = 8 * 4096 * 8 * 8 / 64
    fl = 4 * 4 * 3 * 2 * rows * 2304 * 896              # 4 layers
    by = 4 * 4 * 3 * 2 * (rows * 2304 + 8 * 2304 * 896 + rows * 896)
    peak = flops.peaks("TPU v5 lite")
    least = max(fl / peak["bf16_flops_per_s"], by / peak["hbm_bytes_per_s"])
    # kernel ns: device 0 40..100 and 200..300 (160), device 1 50..150
    # (100); mean 130 over 2 steps
    want = 100 * least / (130 / 2 * 1e-9)
    assert reader(ctx_of(hand_made())) == pytest.approx(want)
    assert reader.__globals__["share"](
        hand_made(), m, 8, 4096, peak) == pytest.approx(want)


def test_roofline_reads_nothing_without_its_parts(monkeypatch):
    reader = harness.load_reader(ROOF)
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload", CELL])
    assert reader(ctx_of(None)) is None
    tr = hand_made()
    tr["devices"] = {d: [op for op in v if "gmm" not in op[0]
                         and "custom-call.5" not in op[0]]
                     for d, v in tr["devices"].items()}
    tr["op_names"] = {d: [""] * len(v) for d, v in tr["devices"].items()}
    assert reader(ctx_of(tr)) is None                 # no kernel op
    monkeypatch.setattr(sys, "argv", ["run.py", "--workload",
                                      "granite-8b-1l.train_4k"])
    assert reader(ctx_of(hand_made())) is None        # no MoE layer
    monkeypatch.setattr(sys, "argv", ["calibrate.py"])
    assert reader(ctx_of(hand_made())) is None        # no cell named
