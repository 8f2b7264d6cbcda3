"""The numbers that decide ``correct``, each against a limit of its own.

The program's first three steps, as the window's own call and feed ran
them, against the reference's three steps from the same weights and rows:

- ``loss_gap``: the largest |loss - reference loss| / |reference loss| over
  the three steps; ``loss_gap_step1`` the same for the first step alone;
- ``grad_norm_gap``: by the worst leaf, the gap between the norm of the
  first gradient as the optimizer got it (AdamW's first moment after one
  step over 1 - b1) and the reference's, over the larger of that leaf's
  reference norm and the median leaf's; ``grad_norm_gap_median`` the median
  leaf's gap;
- ``update_norm_gap``: the same for the norm of each leaf's change over the
  three steps, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone);
  ``update_norm_gap_median`` the median leaf's gap.

A cell compares the numbers its ``limits/<cell>.json`` names.  Two counts
are compared in every cell, with the limit 0: rows of the three steps that
repeat another, and steps of the window whose loss is not finite.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from chipbench import weights

STILL = 1e-3       # a leaf's reference gradient under this x the median's


def leaf_norms(tree) -> jnp.ndarray:
    """L2 norm of each leaf, in ``weights.flatten`` order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in weights.flatten(tree).values()])


def leaf_gaps(prog: Sequence[float], ref: Sequence[float]) -> np.ndarray:
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return np.abs(prog - ref) / np.maximum(ref, np.median(ref))


def moved(ref: dict) -> np.ndarray:
    """Leaves whose reference gradient the update comparison keeps."""
    return ref["grad_norms"] >= STILL * np.median(ref["grad_norms"])


def gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` as ``Reference.run`` returns them."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss = np.abs(lp - lr) / np.abs(lr)
    grad = leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    update = leaf_gaps(prog["delta_norms"], ref["delta_norms"])[moved(ref)]
    return {"loss_gap": float(np.max(loss)),
            "loss_gap_step1": float(loss[0]),
            "grad_norm_gap": float(np.max(grad)),
            "grad_norm_gap_median": float(np.median(grad)),
            "update_norm_gap": float(np.max(update)),
            "update_norm_gap_median": float(np.median(update))}


def repeated_rows(batches) -> int:
    """Rows (token sequences) of ``batches`` equal to an earlier one."""
    seen, dup = set(), 0
    for tokens, _ in batches:
        for row in np.asarray(tokens):
            h = row.tobytes()
            dup += h in seen
            seen.add(h)
    return dup


def checks(readings: Dict[str, float],
           limits: Dict[str, Optional[float]]) -> dict:
    """{name: {"value", "limit"}} for every number ``limits`` names."""
    unknown = set(limits) - set(readings)
    if unknown:
        raise KeyError(f"limits name numbers not read: {sorted(unknown)}")
    return {name: {"value": readings[name], "limit": limit}
            for name, limit in limits.items()}


def passed(chk: dict) -> bool:
    """Every number within its limit; one with no limit, or one that is not
    a number, fails."""
    return all(c["limit"] is not None and np.isfinite(c["value"])
               and c["value"] <= c["limit"] for c in chk.values())
