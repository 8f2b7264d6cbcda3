"""The harness finds every part of a cell by name, from files alone, and
the command refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import tiny
from chipbench import harness, traffic

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
# What a configuration file states, so that neither side takes it from
# elsewhere: the program's registry or the reference's defaults.
STATED = {"n_layers", "d_model", "n_heads", "n_kv", "head_dim", "d_ff",
          "vocab", "mlp", "norm", "norm_eps", "rope_theta", "tie_embeddings",
          "dtype", "scores_dtype", "remat"}


def test_finds_config_traffic_limits_and_readers_by_name(tmp_path):
    bench_dir = tmp_path / "b"
    for sub in ("configs", "traffic", "limits", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "m-1l.json").write_text(json.dumps(
        {"arch": "granite-8b", "model": {"n_layers": 1}}))
    (bench_dir / "traffic" / "mix_a.json").write_text(json.dumps(
        {"kind": "train", "batch": 3, "seq_len": 8, "tokens": "zipf_repeat",
         "packing": "none", "loop": "closed"}))
    (bench_dir / "limits" / "m-1l.mix_a.json").write_text(json.dumps(
        {"limits": {"loss_gap": 0.5}}))
    (bench_dir / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2 * ctx\n")
    (bench_dir / "metrics" / "other_metric.py").write_text(
        "def read(ctx):\n    return None\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "m-1l", "file": "b/configs/m-1l.json"}],
        "workloads": [{"name": "m-1l.mix_a", "config": "m-1l",
                       "traffic": "mix_a", "chips": 4}],
        "end_to_end": [{"name": "new_metric", "unit": "s"}],
        "per_layer": [{"name": "other_metric", "unit": "%",
                       "workloads": ["elsewhere"]}]}))
    cell = harness.load_cell("m-1l.mix_a", root=tmp_path, bench_dir=bench_dir)
    assert cell.chips == 4 and cell.config["model"]["n_layers"] == 1
    assert cell.mix["batch"] == 3 and cell.limits == {"loss_gap": 0.5}
    assert [m["name"] for m in cell.end_to_end] == ["new_metric"]
    assert cell.per_layer == []          # its workloads leave this cell out
    assert harness.load_reader("new_metric", bench_dir)(21) == 42
    assert harness.load_reader("other_metric", bench_dir)(0) is None
    with pytest.raises(KeyError):
        harness.load_cell("m-1l.mix_b", root=tmp_path, bench_dir=bench_dir)


def _same(have, stated):
    """A field of the program's config holds what the file states: a list
    as a tuple, a dict as its dataclass."""
    if isinstance(stated, list):
        return have == tuple(stated)
    if isinstance(stated, dict):
        return all(_same(getattr(have, k), v) for k, v in stated.items())
    return have == stated


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_has_its_files(w):
    cell = harness.load_cell(w)
    m = cell.config["model"]
    assert STATED <= set(m)
    cfg = harness.model_config(cell.config)
    for key, value in m.items():
        if hasattr(cfg, key):
            assert _same(getattr(cfg, key), value), key
    assert cell.config["chips"] == cell.chips
    conf = next(c for c in BENCH["configs"]
                if c["name"] == cell.config["name"])
    for key in conf["reduced"]:
        assert cell.config["published"][key] != m[key]
    for key, value in cell.config["published"].items():
        assert key in conf["reduced"] or m[key] == value, key
    assert cell.limits and all(v > 0 for v in cell.limits.values())
    for spec in cell.end_to_end + cell.per_layer:
        assert callable(harness.load_reader(spec["name"]))
    names = {s["name"] for s in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert {m["moves"] for m in cell.per_layer} <= names


def test_a_block_kind_with_no_file_is_refused_by_name():
    cell = tiny.cell(pattern=["rglru"])
    with pytest.raises(ValueError, match="no block kind 'rglru'"):
        harness.Program(cell.config, cell.mix, jax.devices()[:1])


def test_a_stated_pattern_reaches_the_program():
    model = dict(tiny.MODEL, pattern=["local", "attn"], window=16,
                 moe={"num_experts": 8, "top_k": 2})
    cfg = harness.model_config({"arch": "granite-8b", "model": model})
    assert cfg.pattern == ("local", "attn") and cfg.window == 16
    assert cfg.moe.num_experts == 8 and cfg.moe.top_k == 2
    assert (cfg.d_model, cfg.dtype) == (64, "float32")
    default = harness.model_config({"arch": "granite-8b",
                                    "model": dict(tiny.MODEL)})
    assert default.pattern == ("attn",) and default.moe is None


def test_a_mix_asking_for_what_the_generator_lacks_is_refused():
    mix = {"kind": "train", "batch": 2, "seq_len": 8, "tokens": "zipf_repeat",
           "packing": "documents", "loop": "closed"}
    with pytest.raises(ValueError, match="packing"):
        traffic.check_mix(mix)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "granite-8b-1l.train_4k", "--seed", "5", "--seconds", "1"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_no_result():
    r = _run(harness.ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(harness.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
