"""Layer kinds found by name: the layout, weights, FLOP counts and
reference of the existing cells are those of the layout with one ``attn``
block a layer, and a configuration that mixes ``local`` and ``attn`` layers
runs through the harness against its reference."""
import hashlib
import json

import jax
import numpy as np
import pytest

import tiny
from chipbench import blocks, compare, flops, harness, traffic, weights
from chipbench.reference import Reference

# float32 on both sides, over 6 layers and 3 AdamW steps (test_reference.py).
F32_GAP = 2e-5
LOCAL = {"pattern": ["local", "local", "local", "attn"], "window": 16,
         "n_layers": 6}


def layout_digest(m):
    """Every leaf's place in init's order, path, shape and std: what
    ``weights.init`` makes from a key, bit for bit."""
    h = hashlib.sha256()
    for i, (p, (s, std)) in enumerate(sorted(weights.layout(m).items())):
        h.update(repr((i, p, tuple(s), std)).encode())
    return h.hexdigest()


def init_digest(m, seed):
    w = jax.jit(lambda k: weights.init(k, m))(weights.seed_key(seed))
    h = hashlib.sha256()
    for p, x in weights.flatten(w).items():
        h.update(p.encode())
        h.update(np.asarray(x).tobytes())
    return h.hexdigest()


# Read from the code with one attn block a layer, before kinds were files.
@pytest.mark.parametrize("name,batch,digest,step_flops", [
    ("granite-8b-1l", 2,
     "b218fbe0aa9dddeccb0c302b02875e4ff0c9b29ae5df00b11bf263389ac76fc4",
     22_265_110_462_464),
    ("granite-8b-4l", 8,
     "53a51ce55ebd2b6cbf0268a3637668fef8045ab75a2c62b45aa9ab4a9e116a49",
     237_494_511_599_616)])
def test_granite_layout_and_flops_are_the_parents(name, batch, digest,
                                                  step_flops):
    m = json.loads((harness.BENCH_DIR / "configs" / f"{name}.json")
                   .read_text())["model"]
    assert layout_digest(m) == digest
    assert flops.train_flops_per_step(m, batch, 4096) == step_flops


@pytest.mark.parametrize("tied,digest", [
    (False, "3c2492f6f5fb4616b3fa4ff52cb1a660983d5df619c2c2526d24b6028d350b80"),
    (True, "5acd802fb5633703e27e87728aa14076efa71a729d0fdb6ad206a72deb7a95e2")])
def test_weights_are_the_parents(tied, digest):
    assert init_digest(dict(tiny.MODEL, tie_embeddings=tied),
                       2**31 + 7) == digest


def test_reference_reads_what_the_parents_read():
    cell = tiny.cell()
    m, hp = cell.config["model"], cell.config["optimizer"]
    key = weights.seed_key(11)
    data = traffic.source(harness.model_config(cell.config), cell.mix, 11)
    fed = [(jax.device_get(b["tokens"]), jax.device_get(b["labels"]))
           for b in (data.next_batch() for _ in range(3))]
    got = Reference(m, hp, jax.devices()[:1], 4).run(key, fed)
    np.testing.assert_allclose(
        got["losses"], [6.827604293823242, 6.8410186767578125,
                        6.824899673461914], rtol=1e-6)
    np.testing.assert_allclose(got["grad_norms"], [
        7.880241394042969, 0.176999032497406, 0.8312655687332153,
        0.6323822736740112, 1.2960920333862305, 0.6017505526542664,
        1.2606477737426758, 0.7663522362709045, 0.7577243447303772,
        1.0831999778747559, 0.1556376814842224, 0.13049070537090302],
        rtol=1e-6)
    np.testing.assert_allclose(got["delta_norms"], [
        0.06189575046300888, 0.007385381497442722, 0.12649400532245636,
        0.03923677280545235, 0.06029202416539192, 0.05426677316427231,
        0.0423140674829483, 0.08089416474103928, 0.08170486986637115,
        0.08233010768890381, 0.007065460551530123, 0.007433671969920397],
        rtol=1e-6)


def test_layers_stack_as_the_programs():
    from repro.models import param_shapes
    m = dict(tiny.MODEL, **LOCAL)
    assert blocks.kinds(m) == ["local"] * 3 + ["attn"] + ["local"] * 2
    have = weights.flatten(param_shapes(harness.model_config(
        {"arch": "granite-8b", "model": m})))
    assert {p: x.shape for p, x in weights.flatten(
        weights.shapes(m)).items()} == {p: x.shape for p, x in have.items()}
    assert {p.split("/")[1] for p in have if p.startswith(("scan", "tail"))} \
        == {"s0_local", "s1_local", "s2_local", "s3_attn", "t0_local",
            "t1_local"}


def test_local_layers_count_the_keys_in_their_window():
    m = dict(tiny.MODEL, **LOCAL)
    d, f, v = 64, 128, 512
    layer = d * 4 * 16 + 2 * d * 2 * 16 + 4 * 16 * d + 3 * d * f
    assert flops.matmul_params(m) == 6 * layer + d * v
    assert flops.train_flops_per_token(m, 64) == \
        6 * (6 * layer + d * v) + 12 * 4 * 16 * (5 * 16 + 64)


def _gaps(chips, ref_model=None):
    cell = tiny.cell(chips=chips, **LOCAL)
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
def test_program_with_local_layers_matches_the_reference(chips):
    gaps, limits = _gaps(chips)
    assert max(gaps.values()) < F32_GAP, gaps
    assert compare.passed(compare.checks(gaps, limits)), gaps


def test_reference_without_the_window_fails_the_limits():
    gaps, limits = _gaps(1, {"window": 0})
    assert not compare.passed(compare.checks(gaps, limits)), gaps
