"""attention_kernel_device_ms_per_step: the fused attention kernels' device
time, on a hand-made trace with known answers and on the recorded train_4k
trace of a program without the kernels."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness

DATA = Path(__file__).resolve().parent / "data"
NAME = "attention_kernel_device_ms_per_step"
ATTN = "jit(train_step)/jvp()/while/body/closed_call/attention/"


def ctx_of(tr):
    return SimpleNamespace(trace=tr, step_s=[0.5, 0.5], window_s=1.0,
                           setup_s=7.5, flops_per_step=1.0, chips=1,
                           device_kind="TPU v5 lite")


def hand_made(op_names: bool):
    # window 0..100 with two steps.  Device 0: the forward kernel 10..20,
    # the forward's recomputation 30..38 overlapping a dq found by its
    # op_name alone 35..40, dk/dv 40..52, an attention fusion that is no
    # kernel 52..60, and a forward kernel that starts before the window;
    # device 1: one dq kernel 20..30.
    spans = [["bench.window", 0, 100], ["bench.step", 5, 8],
             ["bench.step", 55, 58]]
    dev0 = [
        ("flash_attention_fwd.2: custom-call bf16[2,4096,4096]", -5, 3,
         ATTN + "flash_attention_fwd/pallas_call"),
        ("while.1: while s32[]", 10, 60, "jit(train_step)/jvp()/while"),
        ("flash_attention_fwd.1: custom-call bf16[2,4096,4096]", 10, 20,
         ATTN + "flash_attention_fwd/pallas_call"),
        ("flash_attention_fwd.3: custom-call bf16[2,4096,4096]", 30, 38,
         ATTN + "rematted_computation/flash_attention_fwd/pallas_call"),
        ("custom-call.7: custom-call bf16[2,4096,4096]", 35, 40,
         "jit(train_step)/transpose(jvp(attention))/flash_attention_dq/"
         "pallas_call"),
        ("flash_attention_dkv: custom-call bf16[2,4096,1024]", 40, 52,
         ATTN + "flash_attention_dkv/pallas_call"),
        ("fusion.3: fusion bf16[2,4096,32,128]", 52, 60, ATTN + "mul")]
    dev1 = [("flash_attention_dq.1: custom-call bf16[2,4096,4096]", 20, 30,
             ATTN + "flash_attention_dq/pallas_call")]
    devs = {"0": dev0, "1": dev1}
    tr = {"host_spans": spans,
          "devices": {d: [[n, s, e] for n, s, e, _ in v]
                      for d, v in devs.items()}}
    if op_names:
        tr["op_names"] = {d: [o for *_, o in v] for d, v in devs.items()}
    return tr


@pytest.mark.parametrize("op_names,device0_ns", [
    (True, 3 + 10 + 10 + 12),     # 0..3, 10..20, 30..40, 40..52
    (False, 3 + 10 + 8 + 12),     # the dq named by its op_name alone is lost
])
def test_kernel_ms_of_the_hand_made_trace(op_names, device0_ns):
    got = harness.load_reader(NAME)(ctx_of(hand_made(op_names)))
    # mean over the two devices (device 1: 10 ns), over two steps, in ms
    assert got == pytest.approx((device0_ns + 10) / 2 / 2 * 1e-6)


def test_no_trace_or_no_step_reads_nothing():
    assert harness.load_reader(NAME)(ctx_of(None)) is None
    tr = hand_made(True)
    tr["host_spans"] = [s for s in tr["host_spans"] if s[0] != "bench.step"]
    assert harness.load_reader(NAME)(ctx_of(tr)) is None


def test_a_program_without_the_kernels_reads_0():
    """The recorded train_4k trace (naive attention, no op_names)."""
    with gzip.open(DATA / "granite-8b-1l.train_4k.trace.json.gz", "rt") as f:
        tr = json.load(f)
    assert harness.load_reader(NAME)(ctx_of(tr)) == 0.0
