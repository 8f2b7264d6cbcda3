"""launch.train.run on a mesh laid over the devices present."""
import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]

# Four virtual CPU devices need XLA_FLAGS before jax initialises, so this
# runs in a child process.
_FOUR_DEVICES = """
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from repro.launch.train import build_argparser, run

args = build_argparser().parse_args(
    ["--arch", "granite-8b", "--smoke", "--steps", "3", "--batch", "4",
     "--seq", "32", "--log-every", "100"])
one = run(args, devices=jax.devices()[:1])
four = run(args)
print(json.dumps({"one": one, "four": four}))
"""


def test_train_on_2x2_mesh_matches_one_device():
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES],
                       capture_output=True, text=True, cwd=REPO,
                       env=dict(os.environ, PYTHONPATH=str(REPO / "src")),
                       timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["one"]["mesh"] == {"data": 1, "model": 1}
    assert out["four"]["mesh"] == {"data": 2, "model": 2}
    assert len(out["four"]["losses"]) == 3
    # f32 smoke config: the 2x2 run sums the same terms in another order
    # (batch halves on data, heads/d_ff halves on model), so the losses
    # differ by f32 rounding only (about 1 ulp, 1e-7, seen); 1e-6 leaves
    # room for that to compound over 3 AdamW steps.
    for a, b in zip(out["one"]["losses"], out["four"]["losses"]):
        assert abs(a - b) <= 1e-6 * abs(a), (out["one"], out["four"])
