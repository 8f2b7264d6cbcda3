"""The float32 reference against the program's train step at a small size
on the CPU.  With the program also in float32 the two differ by float32
rounding only: the same model, loss, gradients and AdamW."""
import jax
import pytest

import tiny
from chipbench import compare, harness, traffic, weights
from chipbench.reference import Reference

# float32 on both sides, over 2 layers and 3 AdamW steps: gaps of a few
# float32 roundings (1.2e-7) times the depth of the sums.
F32_GAP = 2e-5


@pytest.mark.parametrize("mlp,norm,heads,tied", [
    ("swiglu", "rmsnorm", 4, False), ("gelu", "layernorm", 6, False),
    ("swiglu", "rmsnorm", 4, True)])
def test_program_in_float32_matches_the_reference(mlp, norm, heads, tied):
    cell = tiny.cell(mlp=mlp, norm=norm, n_heads=heads, dtype="float32",
                     tie_embeddings=tied)
    m, hp = cell.config["model"], cell.config["optimizer"]
    devices = jax.devices()[:1]
    prog = harness.Program(cell.config, cell.mix, devices)
    seed = 2**31 + 7
    key = weights.seed_key(seed)
    data = traffic.source(prog.cfg, cell.mix, seed)
    _, _, got, fed = harness.program_first_steps(prog, data, key, hp["b1"])
    ref = Reference(m, hp, devices, cell.mix["batch"]).run(key, fed)
    gaps = compare.gaps(got, ref)
    assert max(gaps.values()) < F32_GAP, gaps
    assert compare.repeated_rows(fed) == 0


def test_reference_is_the_same_on_four_devices():
    cell = tiny.cell()
    m, hp = cell.config["model"], cell.config["optimizer"]
    key = weights.seed_key(3)
    data = traffic.source(harness.model_config(cell.config), cell.mix, 3)
    fed = [(jax.device_get(b["tokens"]), jax.device_get(b["labels"]))
           for b in (data.next_batch() for _ in range(3))]
    one = Reference(m, hp, jax.devices()[:1], 4).run(key, fed)
    four = Reference(m, hp, jax.devices()[:4], 4).run(key, fed)
    assert max(compare.gaps(four, one).values()) < F32_GAP


def test_seeds_past_32_bits_give_other_weights_and_rows():
    a, b = (weights.seed_key(s) for s in (5, 2**32 + 5))
    assert not (a == b).all()
    assert traffic.data_seed(5) == 5
    assert traffic.data_seed(2**32 + 5) not in (5, 2**32 + 5)
    assert traffic.data_seed(2**40 + 3) < 2**32
