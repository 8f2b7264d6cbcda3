"""The harness finds every part of a cell by name, from files alone, and
the command refuses to run without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import harness, traffic

BENCH = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


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


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_has_its_files(w):
    cell = harness.load_cell(w)
    m = cell.config["model"]
    assert set(harness.PROGRAM_KEYS) | {"norm_eps"} <= set(m)
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
