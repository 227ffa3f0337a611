"""Mesh definitions.

Functions, not module-level constants, so importing this module never touches jax
device state (jax locks the device count on first backend init — see dryrun.py).
Every mesh has Auto axes (``distributed.sharding.make_mesh``).

Data-par.   : (data=N,) over the first N devices present (all by default) —
              pure data parallel, so the freeze-aware explicit reduce engages:
              ``--mesh local`` on one host of 1 or 4 chips, and an elastic
              fleet's chief with one slot per worker.
Single pod  : (data=16, model=16)            = 256 chips (one v5e pod)
Multi-pod   : (pod=2, data=16, model=16)     = 512 chips; the leading "pod" axis
              carries the slow inter-pod links — batch shards over (pod, data),
              gradient reduction over "pod" is the compressed cross-pod reduce.
"""
from __future__ import annotations

import math
from typing import Optional

import jax

from repro.distributed.sharding import (DEFAULT_RULES, MULTIPOD_RULES,
                                        ShardingRules, make_mesh)


def make_dp_mesh(n: Optional[int] = None):
    """Pure-DP ``("data",)`` mesh over the first ``n`` devices of this
    process (all of them by default).  An elastic fleet's chief (DESIGN.md
    §4b) passes its world size: one slot per fleet worker, laid over the
    host-platform devices the coordinator's XLA_FLAGS forced into it.
    Pure-DP at every width keeps the mesh eligible for the freeze-aware
    explicit reduce, so a resize re-derives the ReducePlan rather than
    silently falling back to GSPMD."""
    devices = jax.devices()[:n]
    if n is not None and len(devices) != n:
        raise ValueError(f"a {n}-way data mesh needs {n} devices, "
                         f"found {len(devices)}")
    return make_mesh((len(devices),), ("data",), devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # 512 placeholder devices: the single-pod mesh uses the first 256
    return make_mesh(shape, axes, jax.devices()[:math.prod(shape)])


def rules_for(mesh) -> ShardingRules:
    return MULTIPOD_RULES if "pod" in mesh.axis_names else DEFAULT_RULES


def chips(mesh) -> int:
    return mesh.devices.size
