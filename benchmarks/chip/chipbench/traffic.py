"""Training traffic: the mix file ``traffic/<name>.json`` and the seed of
its batches.

The batches come from the program's own data pipeline,
``repro.data.SyntheticLM`` (a Zipf-like unigram stream whose every second
token repeats its predecessor plus one), as ``launch/train.run`` draws
them: the draw is a step's host work that trainers pay for, so a change to
it shows in the window.  A mix sets the batch and the sequence length; a
batch is a pure function of the seed and the step number, and the rows the
program was fed are what the reference reads.
"""
from __future__ import annotations

# What a mix file may ask for.  A value outside these needs new code, and
# is refused rather than ignored.
KNOWN = {"kind": {"train"}, "tokens": {"zipf_repeat"},
         "packing": {"none"}, "loop": {"closed"}}


def check_mix(mix: dict) -> dict:
    for key, allowed in KNOWN.items():
        if mix.get(key) not in allowed:
            raise ValueError(f"traffic {key}={mix.get(key)!r}: this "
                             f"generator draws only {sorted(allowed)}")
    for key in ("batch", "seq_len"):
        if not (isinstance(mix.get(key), int) and mix[key] > 0):
            raise ValueError(f"traffic {key} must be a positive int")
    return mix


def data_seed(seed: int) -> int:
    """``SyntheticLM``'s seed for a run's ``--seed``.  Its key keeps 32
    bits, so the bits above them are folded into those."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return (seed ^ (seed >> 32) * 0x9E3779B1) & 0xFFFFFFFF


def source(cfg, mix: dict, seed: int):
    """The program's batch source for a cell: ``next_batch()`` draws the
    next step's rows."""
    from repro.data import SyntheticLM
    return SyntheticLM(cfg, mix["batch"], mix["seq_len"],
                       seed=data_seed(seed))
