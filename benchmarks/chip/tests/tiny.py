"""A cell at a size the tests hold: granite-8b's block structure with small
widths, computing in float32, so that a sound run reads float32 round-off
against the reference and a fault stands out against the limits of
granite-8b-1l.train_4k, which ``cell`` takes by default."""
import copy

from chipbench import harness

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv": 2,
         "head_dim": 16, "d_ff": 128, "vocab": 512, "mlp": "swiglu",
         "norm": "rmsnorm", "norm_eps": 1e-06, "rope_theta": 10000.0,
         "tie_embeddings": False, "dtype": "float32",
         "scores_dtype": "float32", "remat": True}
OPT = {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
       "weight_decay": 0.1}
MIX = {"kind": "train", "batch": 4, "seq_len": 64, "tokens": "zipf_repeat",
       "packing": "none", "loop": "closed"}


def cell(chips=1, limits=None, **model) -> harness.Cell:
    m = dict(MODEL, **model)
    real = harness.load_cell("granite-8b-1l.train_4k")
    return harness.Cell(
        name="tiny", chips=chips,
        config={"arch": "granite-8b", "model": m, "optimizer": dict(OPT)},
        mix=copy.deepcopy(MIX),
        limits=dict(real.limits if limits is None else limits),
        end_to_end=real.end_to_end, per_layer=real.per_layer)
