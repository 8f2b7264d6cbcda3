"""Device time by layer scope and the gaps inside a step: the scope of an
op_name, each scope reader on a hand-made trace with known answers, the
op_name reduction from a profile, the other trace readers, which read the
same with op_names in the trace as without, and a recorded chip trace."""
import gzip
import json
import os
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import harness, scopes, trace

DATA = Path(__file__).resolve().parent / "data"
STEP = "jit(train_step)/"
SCAN = STEP + "transpose(jvp())/while/body/closed_call/checkpoint/"
NEW = {"attention_device_ms_per_step": "attention",
       "mlp_device_ms_per_step": "mlp",
       "lm_head_device_ms_per_step": "lm_head",
       "optimizer_device_ms_per_step": "optimizer"}
OLD = ("device_idle_share", "driver_host_ms_per_step",
       "collective_exposed_ms_per_step")


def hand_made():
    # window 0..100, two steps; device 0 runs step 1 in 10..45 with idle
    # 20..22 and 37..40 inside it, a draw op between the steps, and step 2
    # in 62..96 with idle 70..75; device 1 runs one attention op.
    spans = [["bench.window", 0, 100],
             ["bench.draw", 0, 4], ["bench.device_put", 4, 5],
             ["bench.step", 5, 8], ["bench.fetch_loss", 8, 50],
             ["bench.draw", 50, 54], ["bench.device_put", 54, 55],
             ["bench.step", 55, 58], ["bench.fetch_loss", 58, 100]]
    dev0 = [
        ("fusion.9: fusion u32[2]", 1, 3, "jit(_draw)/threefry2x32"),
        ("while.1: while s32[]", 10, 45, STEP + "jvp()/while"),
        ("fusion.1: fusion bf16[2,8]", 10, 20, STEP + "jvp(embed)/mul"),
        ("fusion.2: fusion f32[2,8]", 22, 30,
         STEP + "jvp()/while/body/closed_call/attention/dot_general"),
        ("fusion.3: fusion f32[8,32]", 28, 36, SCAN + "mlp/dot_general"),
        ("copy-done.1: copy-done f32[8]", 36, 37, ""),
        ("fusion.4: fusion f32[8]", 40, 45, STEP + "optimizer/sub"),
        ("fusion.9: fusion u32[2]", 51, 52, "jit(_draw)/threefry2x32"),
        ("fusion.5: fusion f32[2,8,32]", 62, 70,
         STEP + "jvp(lm_head)/bsd,dv->bsv/dot_general"),
        ("fusion.6: fusion f32[2,8]", 75, 90,
         SCAN + "rematted_computation/attention/exp"),
        ("fusion.7: fusion f32[8]", 90, 96,
         STEP + "transpose(jvp(lm_head))/attention_x/add")]
    dev1 = [("fusion.2: fusion f32[2,8]", 20, 30,
             STEP + "jvp()/while/body/closed_call/attention/dot_general")]
    devs = {"0": dev0, "1": dev1}
    return {"host_spans": spans,
            "devices": {d: [[n, s, e] for n, s, e, _ in v]
                        for d, v in devs.items()},
            "op_names": {d: [o for *_, o in v] for d, v in devs.items()}}


def ctx_of(tr):
    return SimpleNamespace(trace=tr, step_s=[0.5, 0.5], window_s=1.0,
                           setup_s=7.5, flops_per_step=197e12 / 100, chips=1,
                           device_kind="TPU v5 lite")


def test_scope_of_takes_the_innermost_whole_component():
    assert scopes.scope_of(SCAN + "attention/exp") == "attention"
    assert scopes.scope_of(
        STEP + "transpose(jvp(lm_head))/bsd,dv->bsv/dot_general") == "lm_head"
    assert scopes.scope_of(STEP + "jvp(moe)/mlp/dot_general") == "mlp"
    assert scopes.scope_of(STEP + "attention_x/add") is None
    assert scopes.scope_of(STEP + "jvp()/my_attention/add") is None
    assert scopes.scope_of("") is None


def test_scope_buckets_of_the_hand_made_trace():
    got = scopes.scope_ns(hand_made())
    # mean over the two devices; device 1 adds 10 ns of attention
    assert got == {"embed": 5, "attention": (23 + 10) / 2, "mlp": 4,
                   "optimizer": 2.5, "lm_head": 7, None: 2}


def test_readers_on_the_hand_made_trace():
    ctx = ctx_of(hand_made())
    want = {"attention": 8.25e-6, "mlp": 2e-6, "lm_head": 3.5e-6,
            "optimizer": 1.25e-6}
    for name, scope in NEW.items():
        assert harness.load_reader(name)(ctx) == pytest.approx(want[scope])
    # step 1 idles 20..22 and 37..40, step 2 70..75; the gap between the
    # steps (45..62) and the draw's op are not the step's; device 1 has no
    # gap inside its one op
    assert scopes.step_gaps(ctx.trace) == {
        "0": [(20, 22), (37, 40), (70, 75)], "1": []}
    assert harness.load_reader("step_gap_ms_per_step")(ctx) == \
        pytest.approx(2.5e-6)
    ctx.trace = None
    for name in list(NEW) + ["step_gap_ms_per_step"]:
        assert harness.load_reader(name)(ctx) is None


def test_a_program_without_scopes_reads_no_layer_time():
    tr = hand_made()
    tr["op_names"] = {d: [o.replace("attention", "a").replace("mlp", "m")
                          .replace("embed", "e").replace("lm_head", "h")
                          .replace("optimizer", "o") for o in v]
                      for d, v in tr["op_names"].items()}
    for name in NEW:
        assert harness.load_reader(name)(ctx_of(tr)) is None
    assert harness.load_reader("step_gap_ms_per_step")(ctx_of(tr)) == \
        pytest.approx(2.5e-6)


HLO = {"fusion": "%{name} = {type}{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
       "while": "%{name} = s32[] while(s32[] %t), condition=%c, body=%b",
       "copy-done": "%{name} = {type}{{0}} copy-done(f32[8]{{0}} %c)"}


def xspace_text(tr: dict) -> str:
    """A profile in protobuf text form holding ``tr``: bench spans on a
    host plane, and each device's ops as ``XLA Ops`` events whose metadata
    has the HLO text and a ``tf_op`` stat of "<op_name>:"."""
    def plane(pid, name, line, events, metas):
        return (f'planes {{ id: {pid} name: "{name}" '
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 0 {events} }} '
                f'{metas} stat_metadata {{ key: 1 value {{ id: 1 '
                f'name: "{scopes.OP_NAME_STAT}" }} }} }}\n')

    def event(mid, s, e):
        return (f"events {{ metadata_id: {mid} offset_ps: {s * 1000} "
                f"duration_ps: {(e - s) * 1000} }} ")

    def meta(mid, name, op=None):
        stat = (f' stats {{ metadata_id: 1 str_value: "{op}:" }}'
                if op else "")
        return (f'event_metadata {{ key: {mid} value {{ id: {mid} '
                f'name: "{name}"{stat} }} }} ')

    out = plane(0, "/host:CPU", "python",
                "".join(event(k + 1, s, e) for k, (_, s, e)
                        in enumerate(tr["host_spans"])),
                "".join(meta(k + 1, n) for k, (n, _, _)
                        in enumerate(tr["host_spans"])))
    for d, ops in tr["devices"].items():
        metas = []
        for k, ((label, _, _), op) in enumerate(zip(ops, tr["op_names"][d])):
            name, opcode, *ty = label.replace(":", "").split(" ")
            metas.append(meta(k + 1, HLO[opcode].format(
                name=name, type=ty[0] if ty else ""), op))
        out += plane(int(d) + 1, f"/device:TPU:{d}", trace.OPS_LINE,
                     "".join(event(k + 1, s, e) for k, (_, s, e)
                             in enumerate(ops)), "".join(metas))
    return out


def write_xspace(path: Path, tr: dict) -> Path:
    from jax.profiler import ProfileData
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        xspace_text(tr)))
    return path


def test_op_names_come_from_the_profile_the_trace_was_reduced_from(
        tmp_path):
    want = hand_made()
    path = write_xspace(tmp_path / "run" / "a.xplane.pb", want)
    tr = trace.reduce_xspace(path, [0, 1])
    assert tr == {k: want[k] for k in ("host_spans", "devices")}
    assert scopes.reduce_op_names(path, tr) == want["op_names"]
    assert scopes.op_names(tr, tmp_path) == want["op_names"]
    assert tr["op_names"] == want["op_names"]       # kept for later readers
    ctx = ctx_of(tr)
    assert harness.load_reader("attention_device_ms_per_step")(ctx) == \
        pytest.approx(8.25e-6)
    # the newest profile is another run's: its ops are not the trace's
    other = hand_made()
    other["devices"]["0"][3][1] = 23
    newer = write_xspace(tmp_path / "run2" / "b.xplane.pb", other)
    t = path.stat().st_mtime + 10
    os.utime(newer, (t, t))
    tr = trace.reduce_xspace(path, [0, 1])
    assert scopes.op_names(tr, tmp_path) is None
    assert harness.load_reader("mlp_device_ms_per_step")(ctx_of(tr)) is None
    assert scopes.op_names(trace.reduce_xspace(path, [0, 1]),
                           tmp_path / "none") is None


def recorded(name):
    with gzip.open(DATA / name, "rt") as f:
        return json.load(f)


def old_readings(tr):
    ctx = ctx_of(tr)
    return ({n: harness.load_reader(n)(ctx) for n in OLD},
            trace.busy_ns(tr), trace.breakdown(tr))


def test_old_readers_read_the_same_with_op_names(tmp_path):
    """The recorded train_4k fixture, which has no op_names: the trace
    readers and ``breakdown`` read the same with op_names added as without,
    and the scope readers read nothing."""
    tr = recorded("granite-8b-1l.train_4k.trace.json.gz")
    before = old_readings(tr)
    assert before[0]["device_idle_share"] == pytest.approx(
        100 * (1 - 780_114_391 / 813_000_606))
    assert scopes.op_names(tr, tmp_path) is None
    assert scopes.scope_ns(tr) is None
    assert old_readings(tr) == before
    tr["op_names"] = {d: [SCAN + "mlp/dot_general"] * len(v)
                      for d, v in tr["devices"].items()}
    assert scopes.scope_ns(tr)["mlp"] == trace.busy_ns(tr)["0"]
    assert old_readings(tr) == before


def test_recorded_chip_trace_with_scopes():
    """Three steps of granite-8b-1l.train_512 on a TPU v5e with the layer
    scopes: the scopes never overlap and cover 98% of the busy time, the
    busiest op (fusion.95, f32[4096]) is the LM head's backward matmul,
    and the device does not idle inside a step."""
    tr = recorded("granite-8b-1l.train_512.scopes.trace.json.gz")
    ctx = ctx_of(tr)
    got = {scope: harness.load_reader(name)(ctx)
           for name, scope in NEW.items()}
    assert got == pytest.approx({"attention": 24.454134667, "mlp": 76.130670,
                                 "lm_head": 60.145057, "optimizer": 8.365721})
    busy = trace.busy_ns(tr)["0"]
    buckets = scopes.scope_ns(tr)
    assert busy == 536_847_987 and sum(buckets.values()) == busy
    assert buckets[None] == 11_001_035 and buckets["embed"] == 16_454_283
    top = trace.breakdown(tr)["device_ops"][0][0]
    assert top == "fusion.95: fusion f32[4096]"
    assert {scopes.scope_of(o) for (n, _, _), o in
            zip(tr["devices"]["0"], tr["op_names"]["0"]) if n == top} == \
        {"lm_head"}
    assert harness.load_reader("step_gap_ms_per_step")(ctx) == \
        pytest.approx(0.000762)
