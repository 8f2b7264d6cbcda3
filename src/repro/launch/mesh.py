"""Device meshes (defined as functions: importing never touches jax
device state).

``make_mesh`` lays a (data, model) mesh over the devices present: one chip
is (1, 1), a four-chip host (2, 2).

Production (dry-run only):
  single pod: (data=16, model=16)  = 256 chips (TPU v5e pod).
  multi-pod:  (pod=2, data=16, model=16) = 512 chips; the ``pod`` axis rides
  DCN, ``data``/``model`` ride ICI.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(devices: Optional[Sequence] = None):
    """(data, model) mesh over ``devices`` (default: every device present).

    A square count n = k*k gives (k, k); any other count is all data
    parallel, (n, 1).  Axes are ``Auto``: the model's sharding annotations
    are constraints for the partitioner, not types.
    """
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    k = math.isqrt(n)
    shape = (k, k) if k * k == n else (n, 1)
    return jax.make_mesh(shape, ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)
