"""The reference's layer kinds, one module each: ``blocks/<kind>.py``, found
by the kind's name in a configuration's ``model.pattern`` (``["attn"]`` where
the file states none).  Adding a kind adds its file and edits none.

Each module gives, for one layer of its kind:

- ``layout(m)``: ``{path: (shape, std or "ones" / "zeros")}``, paths inside
  the layer as the program's ``transformer._init_block`` names them;
- ``apply(p, x, m, low)``: ``(x_out, extra_loss)``, residuals included, in
  plain float32 ``jax.numpy`` with every product through
  ``reference._mm``.  ``extra_loss`` is the layer's term of the loss beside
  the mean cross-entropy (an MoE's load-balancing and z-loss, which the
  program adds once a step), zero for dense kinds;
- ``matmul_params(m)`` and ``score_flops_per_token(m, seq_len)``, for
  ``flops.py``.

The layers stack as the program stacks them: ``scan/s{i}_{kind}`` for slot
``i`` of the pattern, stacked over the ``n_layers // len(pattern)`` groups,
then ``tail/t{i}_{kind}`` for the remainder, one layer each.
"""
from __future__ import annotations

import importlib
import re
from pathlib import Path
from typing import List, Tuple

HERE = Path(__file__).resolve().parent


def load(kind: str):
    """The module of ``kind``; a kind with no file is refused by name."""
    if not re.fullmatch(r"[a-z][a-z0-9_]*", kind) or \
            not (HERE / f"{kind}.py").is_file():
        raise ValueError(f"no block kind {kind!r}: the reference has no "
                         f"chipbench/blocks/{kind}.py")
    return importlib.import_module(f"{__name__}.{kind}")


def pattern(m: dict) -> Tuple[str, ...]:
    return tuple(m.get("pattern", ("attn",)))


def slots(m: dict) -> Tuple[int, List[Tuple[str, str]], List[Tuple[str, str]]]:
    """(groups, [(path, kind)] of one scan group, [(path, kind)] of the
    tail) for the model dict ``m``."""
    pat = pattern(m)
    groups, tail = divmod(m["n_layers"], len(pat))
    scan = [(f"scan/s{i}_{k}", k) for i, k in enumerate(pat)] if groups else []
    return groups, scan, [(f"tail/t{i}_{k}", k)
                          for i, k in enumerate(pat[:tail])]


def kinds(m: dict) -> List[str]:
    """The kind of every layer, in the order they run."""
    groups, scan, tail = slots(m)
    return [k for _, k in scan] * groups + [k for _, k in tail]
