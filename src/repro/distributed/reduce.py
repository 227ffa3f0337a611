"""Freeze-aware explicit data-parallel gradient reduction (DESIGN.md §3).

Under plain ``jit`` the data-parallel gradient all-reduce is implicit: GSPMD
inserts one collective per gradient leaf during the backward, full-tree, every
step — a frozen matrix keeps paying its entire reduce bandwidth for a gradient
that is exactly zero.  This module makes the reduce *explicit and per-leaf*:
``train/step.py`` computes gradients inside a ``shard_map`` that is manual
over the data-parallel mesh axes (params replicated, batch sharded on its
leading dim) and then calls :func:`reduce_gradients`, which emits one
``lax.pmean`` per live leaf — or per live *row range* for leaves the segment
plan has partially frozen — and skips frozen leaves entirely.  Dropped
gradients are exactly zero on every shard (``stop_gradient`` upstream), so
the skip is bit-identical to reducing them; the bytes simply disappear from
the compiled HLO (the chip benchmark, ``bench/``, has no multi-chip cell to
measure them yet).

Eligibility (:func:`explicit_reduce_axes`): the explicit path engages when the
active mesh is purely data-parallel — every >1-sized axis is a DP axis
(``data`` / ``pod``) — because the loss body runs *manual* on all mesh axes
(tensor-parallel configs keep the implicit GSPMD reduce, where the model-axis
sharding must stay under the compiler).  The Pallas kernels take part: the
flash kernels run unwrapped on each shard inside the manual body, and since
the reduced gradients, parameters and moments are replicated, the GradES
kernels run shard_map-wrapped over replicated specs (``train/step.py``).
``TrainConfig.reduce_mode`` selects ``auto`` (engage when eligible),
``explicit`` (raise when ineligible), or ``implicit`` (never).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.grades import _key_path
from repro.core.partition import ReducePlan

#: Mesh axes the gradient reduce runs over (batch-sharding axes).
DP_AXES = ("pod", "data")


def explicit_reduce_axes(mesh, tcfg) -> Optional[Tuple[str, ...]]:
    """The DP axis names the explicit reduce psums over, or None to keep the
    implicit GSPMD reduce.  See the module docstring for the eligibility
    rules; ``reduce_mode="explicit"`` raises instead of silently falling
    back."""
    mode = getattr(tcfg, "reduce_mode", "auto")
    if mode not in ("auto", "explicit", "implicit"):
        raise ValueError(f"reduce_mode {mode!r}; one of auto|explicit|implicit")
    if mode == "implicit" or mesh is None or mesh.devices.size <= 1:
        if mode == "explicit" and (mesh is None or mesh.devices.size <= 1):
            raise ValueError("reduce_mode='explicit' needs a >1-device mesh")
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(a for a in mesh.axis_names if a in DP_AXES and sizes[a] > 1)
    blockers = []
    if any(sizes[a] > 1 for a in mesh.axis_names if a not in DP_AXES):
        blockers.append("mesh has a >1-sized non-DP axis (tensor parallel)")
    if not axes:
        blockers.append("mesh has no >1-sized data-parallel axis")
    ndev = 1
    for a in axes:
        ndev *= sizes[a]
    if axes and tcfg.global_batch % ndev:
        blockers.append(f"global_batch {tcfg.global_batch} not divisible by "
                        f"the {ndev}-way DP mesh")
    if blockers:
        if mode == "explicit":
            raise ValueError("reduce_mode='explicit' ineligible: "
                             + "; ".join(blockers))
        return None
    return axes


def reduce_gradients(grads, axes: Tuple[str, ...],
                     rplan: Optional[ReducePlan] = None):
    """Per-leaf mean-reduce over the DP ``axes`` inside a manual shard_map
    body, gated by ``rplan`` (None / trivial = full-tree).  Mean (not sum):
    each shard's loss already averages over its local batch rows and the
    shards are equal-sized, so the pmean of shard-means is the global-batch
    mean."""
    lookup = rplan.lookup() if rplan is not None else {}
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
    out = []
    for kp, g in flat:
        ranges = lookup.get(_key_path(kp))
        if ranges is None:
            out.append(jax.lax.pmean(g, axes))
            continue
        if not ranges:
            out.append(g)  # dropped: exactly zero on every shard
            continue
        if len(ranges) == 1 and ranges[0] == (0, g.shape[0]):
            out.append(jax.lax.pmean(g, axes))
            continue
        # Row-sliced leaf: reduce only the live ranges and scatter them into
        # a fresh zeros buffer — the frozen gap rows are exactly zero on
        # every shard, so writing zeros (cheap: no read of g's gaps, no
        # concat copy of the untouched rows) is bit-identical to passing
        # them through.
        acc = jnp.zeros_like(g)
        for lo, hi in ranges:
            acc = jax.lax.dynamic_update_slice_in_dim(
                acc, jax.lax.pmean(g[lo:hi], axes), lo, axis=0)
        out.append(acc)
    return jax.tree_util.tree_unflatten(treedef, out)


def reduce_plan_bytes(tree, rplan: Optional[ReducePlan],
                      bytes_per_elem: int = 4) -> int:
    """Bytes one device contributes to the DP gradient reduce per step under
    ``rplan`` (fp32 wire by default; the int8 path carries 1)."""
    from repro.core.partition import reduce_live_elements
    return reduce_live_elements(tree, rplan) * bytes_per_elem
