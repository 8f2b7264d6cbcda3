"""FLOP counts from shapes against a hand count, and the peak table."""
import json

import pytest

from chipbench import flops, harness


def granite(layers=1):
    return harness.load_cell("granite-8b-1l.train_4k").config["model"] | {
        "n_layers": layers}


def test_granite_8b_one_layer_hand_count():
    d, f, v = 4096, 14336, 49152
    attn = d * 32 * 128 + 2 * d * 8 * 128 + 32 * 128 * d    # q, k, v, o
    mlp = 3 * d * f                                           # SwiGLU
    assert attn == 41_943_040 and mlp == 176_160_768
    assert flops.matmul_params(granite()) == attn + mlp + d * v == 419_430_400
    per_token = 6 * 419_430_400 + 12 * 1 * 32 * 128 * 4096
    assert flops.train_flops_per_token(granite(), 4096) == per_token
    assert flops.train_flops_per_step(granite(), 2, 4096) == \
        8192 * 2_717_908_992 == 22_265_110_462_464


@pytest.mark.parametrize("path", sorted(
    (harness.BENCH_DIR / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_matmul_params_are_the_programs_params_less_gathers_and_norms(path):
    from repro.configs import get_config
    from repro.models import param_count
    config = json.loads(path.read_text())
    m = config["model"]
    cfg = get_config(config["arch"]).replace(n_layers=m["n_layers"])
    norm = m["d_model"] * (2 if m["norm"] == "layernorm" else 1)
    embed = m["vocab"] * m["d_model"]
    assert param_count(cfg) == flops.matmul_params(m) + embed \
        + norm * (2 * m["n_layers"] + 1)


def test_peaks_by_device_kind():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert flops.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
