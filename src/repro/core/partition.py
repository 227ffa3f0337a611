"""Tier-1 static repartition and the Tier-1.5 segment planner (DESIGN.md §2).

Two levels of "static freeze" compose here, both driven by the tiny host-side
copies of ``state.grades.frozen``:

* **Whole-type (Tier 1).**  Once every (layer, expert) instance of a matrix
  *type* is frozen, the host re-jits ``train_step`` with that type's stacked
  parameter wrapped in ``stop_gradient``: XLA dead-code-eliminates the dW
  einsums for the type across every layer — the TPU-native analogue of
  ``requires_grad=False``.
* **Per-layer segments (Tier 1.5).**  During the long per-layer freeze
  wavefront, whole-type elimination never fires even though most rows of a
  type are frozen.  :func:`segment_plan` converts the per-layer masks into a
  :class:`SegmentPlan`: layers are partitioned into contiguous runs whose
  *freeze signature* (the set of types frozen at every layer of the run) is
  equal, and the model replaces its single layer ``lax.scan`` with a chain of
  per-segment scans, each applying ``stop_gradient`` to exactly its
  signature's types (``models/transformer.py``).  Backward dW FLOPs then fall
  with the frozen fraction instead of cliff-dropping at all-frozen.

Recompile bound (the "boundary hysteresis").  The planner is a *pure function
of the masks* — a resumed run recompiles the identical plan — and quantizes
segment boundaries onto a fixed grid of ``segment_max`` cells (cell width
``ceil(L / segment_max)``); a cell's signature is the intersection of its
layers' signatures, and equal-signature neighbours are coalesced.  Boundaries
therefore never track the wavefront layer-by-layer: a cell's signature grows
only when the wavefront *completes* the cell.  Since per-layer signatures are
monotone under GradES freezing, each cell signature is a monotone-growing
intersection, so the plan changes at most once per (cell, type):

    recompiles  ≤  segment_max · n_types       (regression-tested)

versus ~L · n_types for a planner that chases every per-layer freeze.

Everything the host derives from one boundary's masks travels as one
:class:`FreezePlan` (:func:`freeze_plan`): the whole-type set, the segment
plan, the packed-row masks, the :class:`ReducePlan` and the moment layout
``trainable``.  It is *static* per compiled step, and it compares equal
exactly when the compiled step would be the same.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import AbstractSet, Any, Dict, FrozenSet, List, Optional, Tuple

import jax
import numpy as np

from repro.core.grades import MonitorSpec, _key_path


def fully_frozen_types(frozen_host: Dict[str, "np.ndarray"]) -> FrozenSet[str]:
    """Host-side: groups whose every (layer, expert) instance is frozen.

    ``frozen_host`` is the device ``state.grades.frozen`` pulled back with
    ``jax.device_get`` (a few bools per matrix type — trivially cheap).
    """
    return frozenset(name for name, m in frozen_host.items() if bool(np.all(m)))


def _static_paths(spec: MonitorSpec, static_frozen: AbstractSet[str]):
    return {p for name in static_frozen if name in spec.groups
            for p in spec.groups[name][0]}


def static_freeze_tree(params, spec: MonitorSpec,
                       static_frozen: AbstractSet[str]):
    """Apply stop_gradient to every param path of the statically-frozen groups
    (one flatten/unflatten pass, not a per-path nested-dict rebuild)."""
    frozen_paths = _static_paths(spec, static_frozen)
    if not frozen_paths:
        return params
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = [jax.lax.stop_gradient(leaf) if _key_path(kp) in frozen_paths
              else leaf for kp, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def trainable_mask(params, spec: MonitorSpec,
                   static_frozen: AbstractSet[str],
                   row_frozen: Optional[Dict[str, "np.ndarray"]] = None):
    """Pytree declaring which optimizer-moment storage each param needs.

    Leaf values (consumed by ``optim/optimizer.py``):

    * ``True``  — fully live: full-shape m/v buffers.
    * ``False`` — statically frozen (whole type, or every row), or one of
      the model's buffers (``models/moe.py::BUFFERS``): 1-element moment
      placeholder, no update.
    * ``np.ndarray`` (bool, granularity shape, True = **live** row) — the
      Tier-1.5 per-row case: m/v store only the live rows
      (``(n_live,) + trailing``), freeing 8 bytes/param for frozen rows
      *before* the whole type freezes.  This function supports arbitrary
      per-(layer, expert) masks; the trainer's plan-keyed source
      (:func:`plan_row_masks`) emits whole-layer rows, so ``(L, E)`` types
      free per layer-row rather than per expert (see :func:`plan_signature`).

    ``row_frozen`` should be the **plan-quantized** masks from
    :func:`plan_row_masks` (as :func:`freeze_plan` passes them), not the raw
    device masks, which would change the moment layout on every per-layer
    freeze.  None keeps the whole-type behavior (multi-device meshes, where
    packed rows would break the divisibility of the moment shardings).
    """
    # imported here: repro.models reaches back into repro.core
    from repro.models.moe import BUFFERS
    frozen_paths = _static_paths(spec, static_frozen)
    p2g = spec.path_to_group
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    for kp, leaf in flat:
        path = _key_path(kp)
        if path in frozen_paths or str(path[-1]) in BUFFERS:
            leaves.append(False)
            continue
        group = p2g.get(path)
        if row_frozen is None or group is None or group not in row_frozen:
            leaves.append(True)
            continue
        mask = np.asarray(row_frozen[group], bool)
        if not mask.any():
            leaves.append(True)
        elif mask.all():
            leaves.append(False)
        else:
            leaves.append(~mask)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Tier 1.5: the segment planner
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SegmentPlan:
    """A chain of layer segments for the model's scan (DESIGN.md §2).

    ``segments`` is a tuple of ``(lo, hi, signature)`` triples covering
    ``[0, n_layers)`` contiguously; ``signature`` is the frozenset of
    layer-subtree keys (e.g. ``"wq"``) whose dW is eliminated for every layer
    in ``[lo, hi)`` via ``stop_gradient``.  Hashable and comparable — the
    host re-jits exactly when the plan value changes.
    """

    segments: Tuple[Tuple[int, int, FrozenSet[str]], ...]

    @property
    def trivial(self) -> bool:
        """One segment, nothing frozen: identical HLO to the monolithic scan."""
        return len(self.segments) == 1 and not self.segments[0][2]

    @property
    def n_layers(self) -> int:
        return self.segments[-1][1] if self.segments else 0


def plan_signature(frozen_host: Dict[str, "np.ndarray"], spec: MonitorSpec,
                   n_layers: int) -> List[FrozenSet[str]]:
    """Per-layer freeze signature: the group names frozen at each layer.

    A granularity-2 ``(L, E)`` group contributes a layer iff *all* its experts
    are frozen there (per-layer, not all-or-nothing over the whole type).
    Partially-frozen expert rows stay at Tier 0: their dW and their moments
    wait until the full layer row freezes and the plan adopts it —
    finer-than-layer packing would change the moment layout on freezes the
    quantized plan ignores, breaking the recompile bound.
    """
    sigs: List[set] = [set() for _ in range(n_layers)]
    for name in spec.groups:
        m = np.asarray(frozen_host.get(name, False), bool)
        if m.ndim < 1 or m.shape[0] != n_layers:
            continue  # not a stacked-layer group; no per-layer skip possible
        per_layer = m if m.ndim == 1 else m.reshape(m.shape[0], -1).all(axis=1)
        for l in np.nonzero(per_layer)[0]:
            sigs[int(l)].add(name)
    return [frozenset(s) for s in sigs]


def _layer_keys(spec: MonitorSpec, groups: AbstractSet[str]) -> FrozenSet[str]:
    """Map group names to the layer-subtree keys the model applies
    stop_gradient to (``"layers/wq" -> "wq"``; LoRA a/b pairs share a key)."""
    keys = set()
    for name in groups:
        for path in spec.groups[name][0]:
            if len(path) >= 2 and str(path[0]) == "layers":
                keys.add(str(path[1]))
    return frozenset(keys)


def segment_plan(frozen_host: Dict[str, "np.ndarray"], spec: MonitorSpec,
                 n_layers: int, segment_max: int) -> SegmentPlan:
    """Partition layers into ≤ ``segment_max`` equal-signature segments.

    Pure function of the masks (resume-deterministic).  Boundaries are
    quantized onto a ``segment_max``-cell grid and a cell's signature is the
    intersection of its layers' signatures (conservative: a type's dW is only
    skipped where *every* layer of the segment has it frozen), then
    equal-signature neighbours are coalesced — see the module docstring for
    the resulting ``segment_max · n_types`` recompile bound.
    """
    segment_max = max(int(segment_max), 1)
    if n_layers <= 0:
        return SegmentPlan(segments=())
    sigs = plan_signature(frozen_host, spec, n_layers)
    q = -(-n_layers // segment_max)  # ceil: grid cell width
    cells: List[Tuple[int, int, FrozenSet[str]]] = []
    for lo in range(0, n_layers, q):
        hi = min(lo + q, n_layers)
        sig = frozenset.intersection(*sigs[lo:hi])
        cells.append((lo, hi, sig))
    merged = [cells[0]]
    for lo, hi, sig in cells[1:]:
        plo, _, psig = merged[-1]
        if psig == sig:
            merged[-1] = (plo, hi, sig)
        else:
            merged.append((lo, hi, sig))
    return SegmentPlan(segments=tuple(
        (lo, hi, _layer_keys(spec, sig)) for lo, hi, sig in merged))


def plan_row_masks(plan: Optional[SegmentPlan], spec: MonitorSpec,
                   frozen_host: Dict[str, "np.ndarray"]
                   ) -> Optional[Dict[str, "np.ndarray"]]:
    """Per-group frozen-row masks implied by the plan's skip set — the source
    for Tier-1.5 moment packing (``trainable_mask(row_frozen=...)``).

    Keying packing to the *plan* (itself a pure, quantized function of the
    masks) rather than to the raw masks means the moment layout changes only
    when the plan changes: the ``segment_max · n_types`` recompile bound
    covers repacking too, and a resumed run re-derives the checkpoint's
    stored layout from the restored masks alone.  Conservative by design:
    rows the wavefront froze but the quantized plan has not yet adopted keep
    full moments until the next plan change (they are already update-masked
    at Tier 0).  A plan-skipped layer is frozen across every expert by
    construction of the signature, so packing it is always safe.
    """
    if plan is None:
        return None
    L = plan.n_layers
    out: Dict[str, "np.ndarray"] = {}
    for name in spec.groups:
        m = np.asarray(frozen_host.get(name, False), bool)
        if m.ndim < 1 or m.shape[0] != L:
            out[name] = np.zeros_like(m)  # non-stacked: never packed
            continue
        keys = _layer_keys(spec, {name})
        per_layer = np.zeros(L, bool)
        for lo, hi, sig in plan.segments:
            if keys & sig:
                per_layer[lo:hi] = True
        out[name] = np.broadcast_to(
            per_layer.reshape((L,) + (1,) * (m.ndim - 1)), m.shape).copy()
    return out


# ---------------------------------------------------------------------------
# Freeze-aware gradient reduction: the reduce plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ReducePlan:
    """Which gradient leaves (and which of their layer rows) still need the
    data-parallel all-reduce (DESIGN.md §3).

    ``entries`` maps a param path to its live layer ranges:

    * path absent            — fully live: reduce the whole leaf (the default,
      so unmonitored leaves never appear here);
    * ``()``                 — dropped: every row's dW is eliminated
      (``stop_gradient``), the gradient is exactly zero on every shard, and
      skipping the collective is bit-identical to reducing zeros;
    * ``((lo, hi), ...)``    — only axis-0 rows in the (merged, disjoint,
      ascending) ranges are reduced; the gap rows are segment-plan-frozen and
      pass through as exact zeros.

    Hashable and comparable like :class:`SegmentPlan`; it is a pure function
    of ``(static_frozen, plan)``, so a :class:`FreezePlan`'s comparison covers
    it and the ``segment_max · n_types`` bound still holds.
    """

    entries: Tuple[Tuple[Tuple[str, ...],
                         Tuple[Tuple[int, int], ...]], ...] = ()

    @property
    def trivial(self) -> bool:
        """Nothing frozen: identical collectives to the full-tree reduce."""
        return not self.entries

    def lookup(self) -> Dict[Tuple[str, ...], Tuple[Tuple[int, int], ...]]:
        return dict(self.entries)


def _merge_ranges(ranges: List[Tuple[int, int]]) -> Tuple[Tuple[int, int], ...]:
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(ranges):
        if out and out[-1][1] == lo:
            out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return tuple(out)


def gradient_reduce_plan(spec: MonitorSpec,
                         static_frozen: AbstractSet[str],
                         plan: Optional[SegmentPlan],
                         n_layers: int) -> ReducePlan:
    """Derive the reduce plan from the Tier-1/1.5 freeze artifacts.

    Pure in ``(spec, static_frozen, plan)`` — the same boundary masks that
    produced the segment plan produce this, so a resumed run re-derives it
    identically and the recompile count is bounded by the plan's grid
    quantization.  Soundness leans on exactly the mechanisms that make the dW
    elimination itself correct: a ``static_frozen`` type's whole stacked leaf
    is under ``stop_gradient`` (gradient exactly zero ⇒ drop), and a
    plan-skipped segment's layer rows are under the per-segment
    ``stop_gradient`` of the segmented scan (rows exactly zero ⇒ slice them
    out of the psum).  Rows the wavefront froze but the quantized plan has not
    adopted still produce (masked-at-Tier-0, nonzero) gradients, so they keep
    their reduce until the plan catches up — conservative, like the moment
    packing.
    """
    entries: List[Tuple[Tuple[str, ...], Tuple[Tuple[int, int], ...]]] = []
    for name in sorted(spec.groups):
        paths, _ = spec.groups[name]
        if name in static_frozen:
            entries.extend((p, ()) for p in sorted(paths))
            continue
        if plan is None or n_layers <= 0:
            continue
        keys = _layer_keys(spec, {name})
        if not keys:
            continue  # non-stacked group: no per-row dW elimination to mirror
        live = [(lo, hi) for lo, hi, sig in plan.segments if not (keys & sig)]
        if len(live) == len(plan.segments):
            continue  # nothing plan-frozen: full reduce (no entry)
        merged = _merge_ranges(live)
        entries.extend((p, merged) for p in sorted(paths))
    return ReducePlan(entries=tuple(sorted(entries)))


# ---------------------------------------------------------------------------
# The freeze plan: every artifact of one boundary's masks, as one value
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FreezePlan:
    """What the host derives from one boundary's frozen masks, and what a
    compiled step is built for (DESIGN.md §2): the Tier-1 ``whole``-type set,
    the Tier-1.5 ``segments`` (None for a family whose forward consumes
    none), the packed-row masks ``rows`` (None where moments are not
    row-packed), the ``reduce`` plan and the moment layout ``trainable``.

    Equality and hash cover ``whole`` and ``segments`` only: the other three
    are pure functions of those two (and of the run's fixed switches), so two
    plans compare equal exactly when they compile to the same step."""

    whole: FrozenSet[str]
    segments: Optional[SegmentPlan]
    rows: Optional[Dict[str, "np.ndarray"]] = field(compare=False)
    reduce: ReducePlan = field(compare=False)
    trainable: Any = field(compare=False)


def freeze_plan(frozen_host: Dict[str, "np.ndarray"], params,
                spec: MonitorSpec, n_layers: int, segment_max: int, *,
                segments: bool = True, pack_rows: bool = True) -> FreezePlan:
    """The :class:`FreezePlan` of the host masks ``frozen_host`` for the
    tree ``params`` (arrays or ShapeDtypeStructs).  ``segments`` False
    derives no segment plan; ``pack_rows`` False keeps moments unpacked."""
    whole = fully_frozen_types(frozen_host)
    plan = (segment_plan(frozen_host, spec, n_layers, segment_max)
            if segments else None)
    rows = plan_row_masks(plan, spec, frozen_host) if pack_rows else None
    return FreezePlan(whole=whole, segments=plan, rows=rows,
                      reduce=gradient_reduce_plan(spec, whole, plan, n_layers),
                      trainable=trainable_mask(params, spec, whole, rows))


def reduce_live_elements(tree, rplan: Optional[ReducePlan]) -> int:
    """Element count entering the data-parallel reduce under ``rplan`` —
    static accounting for the bench/roofline byte curves (arrays or
    ShapeDtypeStructs; ``None``/trivial plan counts everything)."""
    lookup = rplan.lookup() if rplan is not None else {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    total = 0
    for kp, leaf in flat:
        ranges = lookup.get(_key_path(kp))
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        if ranges is None:
            total += n
        elif len(ranges) and leaf.shape:
            per_row = n // leaf.shape[0]
            total += per_row * sum(hi - lo for lo, hi in ranges)
    return total


def plan_skipped_params(plan: Optional[SegmentPlan], layers,
                        n_layers: int) -> int:
    """Parameter count whose dW the plan's stop_gradient eliminates.

    ``layers`` is the stacked layer-param subtree (arrays or
    ShapeDtypeStructs); per-row count = leaf size / n_layers.  Feeds the
    roofline's frozen-fraction dW term (``launch/roofline.py``, DESIGN.md §8).
    Counts *stored* rows: for MoE expert stacks this is the all-expert count,
    while the 6·N·D FLOP budget uses active (top_k) params —
    ``model_flops_for`` caps the dW credit at the active monitored pool to
    keep the units consistent.
    """
    if plan is None or n_layers <= 0:
        return 0
    total = 0
    for lo, hi, sig in plan.segments:
        for key in sig:
            if key not in layers:
                continue
            leaf_sz = sum(int(np.prod(l.shape))
                          for l in jax.tree.leaves(layers[key]))
            total += (hi - lo) * (leaf_sz // n_layers)
    return total
