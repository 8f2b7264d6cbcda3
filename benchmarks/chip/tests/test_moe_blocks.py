"""Block kinds ``moe`` and ``local_moe``: their layout is the program's,
the reference follows the program on a small Mellum-shaped stage (sliding
and full layers, YaRN, a held block of experts) and leaves it where YaRN or
the held block is taken away, and the FLOP counts of mellum2-12b-4l."""
import json

import jax
import pytest

import tiny
from chipbench import blocks, compare, flops, harness, traffic, weights
from chipbench.reference import Reference

CONFIG = json.loads((harness.BENCH_DIR / "configs" / "mellum2-12b-4l.json")
                    .read_text())
CELL = "mellum2-12b-4l.train_4k_b8"
# float32 on both sides, over 8 layers and 3 AdamW steps.
F32_GAP = 2e-5
MELLUM = {"pattern": ["local_moe"] * 3 + ["moe"], "n_layers": 8,
          "window": 16,
          "yarn": {"factor": 4.0, "original_max_position": 32,
                   "beta_fast": 32.0, "beta_slow": 1.0,
                   "attention_factor": 1.1},
          "moe": {"num_experts": 8, "top_k": 2, "d_expert": 32,
                  "num_shared": 0, "held": 4, "first_held": 2,
                  "aux_coef": 0.01, "router_z_coef": 0.001}}


def program_layout(config):
    from repro.models import param_shapes
    have = weights.flatten(param_shapes(harness.model_config(config)))
    return {p: x.shape for p, x in have.items()}


@pytest.mark.parametrize("model", [CONFIG["model"],
                                   dict(tiny.MODEL, **MELLUM)],
                         ids=["mellum2-12b-4l", "tiny"])
def test_layout_is_the_programs(model):
    config = {"arch": "mellum2-12b", "model": model}
    want = {p: x.shape for p, x in weights.flatten(
        weights.shapes(model)).items()}
    assert want == program_layout(config)
    kinds = {p.split("/")[1] for p in want if p.startswith("scan")}
    assert kinds == {"s0_local_moe", "s1_local_moe", "s2_local_moe",
                     "s3_moe"}


def test_mellum_stage_holds_eight_experts_and_an_eighth_of_the_vocab():
    m = CONFIG["model"]
    shapes = weights.flatten(weights.shapes(m))
    assert shapes["scan/s3_moe/moe/experts/wi"].shape == (1, 8, 2304, 896)
    assert shapes["scan/s0_local_moe/moe/router/w"].shape == (1, 2304, 64)
    assert shapes["embed"].shape == (12288, 2304)
    assert shapes["lm_head"].shape == (2304, 12288)
    n = sum(x.size for x in shapes.values())
    assert n == 340_349_184            # 16 B each with AdamW: 5.45 GB


def test_mellum_flops_hand_count():
    m = CONFIG["model"]
    d, f, v = 2304, 896, 12288
    attn = d * 32 * 128 + 2 * d * 4 * 128 + 32 * 128 * d
    layer = attn + d * 64 + 3 * d * f * 8 * 8 // 64     # one expert's worth
    assert flops.matmul_params(m) == 4 * layer + d * v == 138_608_640
    scores = 12 * 32 * 128 * (3 * 1024 + 4096)
    assert flops.train_flops_per_token(m, 4096) == \
        6 * 138_608_640 + scores
    assert flops.train_flops_per_step(m, 8, 4096) == 38_796_439_584_768


def test_expert_products_hand_count():
    m = CONFIG["model"]
    rows = 8 * 4096 * 8 * 8 / 64                        # 32768
    fl, by = blocks.load("moe").expert_products(m, 8, 4096)
    assert fl == 4 * 3 * 2 * rows * 2304 * 896
    assert by == 4 * 3 * 2 * (rows * 2304 + 8 * 2304 * 896 + rows * 896)
    assert blocks.load("local_moe").expert_products(m, 8, 4096) == (fl, by)


def _gaps(chips, ref_model=None):
    limits = harness.load_cell(CELL).limits
    cell = tiny.cell(chips=chips, limits=limits, **MELLUM)
    cell.config["arch"] = "mellum2-12b"
    m, hp = cell.config["model"], cell.config["optimizer"]
    devices = jax.devices()[:chips]
    prog = harness.Program(cell.config, cell.mix, devices)
    seed = 2**33 + 15
    key = weights.seed_key(seed)
    data = traffic.source(prog.cfg, cell.mix, seed)
    _, _, got, fed = harness.program_first_steps(prog, data, key, hp["b1"])
    ref = Reference(dict(m, **(ref_model or {})), hp, devices,
                    cell.mix["batch"]).run(key, fed)
    return compare.gaps(got, ref), cell.limits


@pytest.mark.parametrize("chips", [1, 4])
def test_program_matches_the_reference(chips):
    """One device runs the grouped-matmul kernel (interpreted), a 2x2 mesh
    ``lax.ragged_dot``; both read float32 round-off against the reference
    and pass the cell's limits."""
    gaps, limits = _gaps(chips)
    assert max(gaps.values()) < F32_GAP, gaps
    assert compare.passed(compare.checks(gaps, limits)), gaps


@pytest.mark.parametrize("change", [
    {"yarn": None},
    {"moe": dict(MELLUM["moe"], first_held=0)},
    {"window": 0}], ids=["no_yarn", "other_experts", "no_window"])
def test_a_reference_without_the_mechanism_fails_the_limits(change):
    gaps, limits = _gaps(1, change)
    assert not compare.passed(compare.checks(gaps, limits)), gaps


@pytest.mark.parametrize("reading", ["lower", "control", "half_batch",
                                     "unchanged_state"])
def test_limits_separate_the_recorded_readings(reading):
    """Through the comparison that decides ``correct``, the cell's limits
    pass the largest sound readings on the chip and fail the float8
    control, the half batch and a state left unchanged; each limit lies
    between its lower and upper reading."""
    rec = json.loads((harness.BENCH_DIR / "limits" / f"{CELL}.json")
                     .read_text())
    limits = rec["limits"]
    assert "update_norm_gap" in limits
    for name, limit in limits.items():
        assert rec["lower"][name] < limit < rec["upper"][name], name
    got = rec[reading]
    chk = compare.checks(got, {n: v for n, v in limits.items() if n in got})
    assert compare.passed(chk) == (reading == "lower"), chk
