"""Tier-1 / Tier-1.5 partition unit tests: per-layer signatures, the segment
planner's grid quantization + coalescing + recompile bound, per-row trainable
masks (incl. the MoE per-expert path), the freeze plan that carries them, and
the dW skip accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.grades import build_monitor_spec
from repro.core.partition import (SegmentPlan, freeze_plan,
                                  fully_frozen_types, gradient_reduce_plan,
                                  plan_row_masks, plan_signature,
                                  plan_skipped_params, segment_plan,
                                  trainable_mask)

L, E, M, N = 8, 2, 4, 16


def make_params():
    k = jax.random.PRNGKey(0)
    return {
        "embed": jnp.ones((16, 4)),
        "layers": {
            "wq": jax.random.normal(k, (L, M, N)),
            "w_up": jax.random.normal(k, (L, M, N)),
            "w_gate": jax.random.normal(k, (L, E, M, N)),  # gran-2 experts
        },
    }


def masks(spec, **overrides):
    out = {}
    for name, (paths, gran) in spec.groups.items():
        shape = (L,) if gran == 1 else (L, E)
        out[name] = overrides.get(name, np.zeros(shape, bool))
    return out


def test_plan_signature_per_layer_and_per_expert():
    spec = build_monitor_spec(make_params())
    gate = np.zeros((L, E), bool)
    gate[0] = True          # layer 0: all experts frozen -> in signature
    gate[1, 0] = True       # layer 1: one expert -> NOT in signature
    fh = masks(spec, **{"layers/wq": np.arange(L) < 2,
                        "layers/w_gate": gate})
    sigs = plan_signature(fh, spec, L)
    assert sigs[0] == frozenset({"layers/wq", "layers/w_gate"})
    assert sigs[1] == frozenset({"layers/wq"})   # partial experts excluded
    assert sigs[2] == frozenset()


def test_fully_frozen_types_all_or_nothing():
    spec = build_monitor_spec(make_params())
    fh = masks(spec, **{"layers/wq": np.ones(L, bool),
                        "layers/w_gate": np.ones((L, E), bool)})
    fh["layers/w_gate"][3, 1] = False
    assert fully_frozen_types(fh) == frozenset({"layers/wq"})


def test_segment_plan_trivial_and_coalesced():
    spec = build_monitor_spec(make_params())
    plan = segment_plan(masks(spec), spec, L, segment_max=4)
    assert plan.trivial and plan.segments == ((0, L, frozenset()),)
    # wavefront: wq frozen in layers [0, 4) -> two segments on the q=2 grid,
    # signatures carry layer-subtree keys
    fh = masks(spec, **{"layers/wq": np.arange(L) < 4})
    plan = segment_plan(fh, spec, L, segment_max=4)
    assert plan.segments == ((0, 4, frozenset({"wq"})), (4, 8, frozenset()))
    assert plan.n_layers == L


def test_segment_plan_quantizes_boundaries():
    """Boundary hysteresis: the wavefront tip inside a grid cell does not move
    the segment boundary — the cell's signature grows only when the wavefront
    completes the cell (this is what bounds recompiles)."""
    spec = build_monitor_spec(make_params())
    p3 = segment_plan(masks(spec, **{"layers/wq": np.arange(L) < 3}),
                      spec, L, segment_max=4)
    p2 = segment_plan(masks(spec, **{"layers/wq": np.arange(L) < 2}),
                      spec, L, segment_max=4)
    assert p3 == p2  # layer 2's freeze is mid-cell: same plan, no recompile


def test_segment_plan_respects_cap():
    spec = build_monitor_spec(make_params())
    # alternating freeze pattern: maximal equal-signature runs would need L
    # segments; the grid caps it
    fh = masks(spec, **{"layers/wq": np.arange(L) % 2 == 0})
    for cap in (1, 2, 4):
        plan = segment_plan(fh, spec, L, segment_max=cap)
        assert len(plan.segments) <= cap
        assert plan.segments[0][0] == 0 and plan.segments[-1][1] == L
        for (_, hi_a, _), (lo_b, _, _) in zip(plan.segments, plan.segments[1:]):
            assert hi_a == lo_b


def test_recompile_budget_over_scripted_wavefront():
    """The documented bound: across a full monotone freeze sequence (every
    (layer, type) flips once, one flip per boundary), the number of *distinct
    consecutive plans* stays <= segment_max * n_types — vs ~L * n_types for a
    planner that chases the wavefront layer by layer."""
    spec = build_monitor_spec(make_params())
    names = sorted(spec.groups)
    seg_max = 4
    fh = masks(spec)
    plans = [segment_plan(fh, spec, L, seg_max)]
    events = 0
    for name in names:
        for l in range(L):
            m = fh[name]
            fh[name] = m.copy()
            fh[name][l] = True  # gran-2: freezes the whole layer row at once
            events += 1
            plans.append(segment_plan(fh, spec, L, seg_max))
    changes = sum(1 for a, b in zip(plans, plans[1:]) if a != b)
    assert events == L * len(names)
    assert changes <= seg_max * len(names), (changes, seg_max, len(names))
    assert changes > 0
    # terminal plan: everything frozen -> one segment, all types skipped
    assert len(plans[-1].segments) == 1
    assert plans[-1].segments[0][2] == frozenset({"wq", "w_up", "w_gate"})


def test_trainable_mask_per_row_and_moe():
    params = make_params()
    spec = build_monitor_spec(params)
    gate = np.zeros((L, E), bool)
    gate[0, 1] = True       # one expert frozen -> per-row, not all-or-nothing
    fh = masks(spec, **{"layers/wq": np.arange(L) < 3,
                        "layers/w_up": np.ones(L, bool),
                        "layers/w_gate": gate})
    t = trainable_mask(params, spec, fully_frozen_types(fh), fh)
    assert t["embed"] is True                       # unmonitored
    assert t["layers"]["w_up"] is False             # fully frozen -> placeholder
    np.testing.assert_array_equal(t["layers"]["wq"], ~fh["layers/wq"])
    np.testing.assert_array_equal(t["layers"]["w_gate"], ~gate)
    # legacy behavior preserved without row masks
    t0 = trainable_mask(params, spec, frozenset(), None)
    assert t0["layers"]["wq"] is True


def test_plan_row_masks_keyed_to_plan():
    """Moment packing follows the plan's (quantized) skip set, not the raw
    masks — the wavefront tip mid-cell frees no rows yet, so the layout (and
    hence the re-jit count) changes only when the plan does."""
    spec = build_monitor_spec(make_params())
    fh = masks(spec, **{"layers/wq": np.arange(L) < 3})  # tip mid-cell (q=2)
    plan = segment_plan(fh, spec, L, segment_max=4)
    rows = plan_row_masks(plan, spec, fh)
    np.testing.assert_array_equal(rows["layers/wq"], np.arange(L) < 2)
    assert not rows["layers/w_up"].any()
    # gran-2 masks broadcast the plan's per-layer decision over experts
    gate = np.ones((L, E), bool)
    fh = masks(spec, **{"layers/w_gate": gate})
    plan = segment_plan(fh, spec, L, segment_max=4)
    rows = plan_row_masks(plan, spec, fh)
    assert rows["layers/w_gate"].shape == (L, E)
    assert rows["layers/w_gate"].all()
    assert plan_row_masks(None, spec, fh) is None


def test_plan_skipped_params():
    params = make_params()
    spec = build_monitor_spec(params)
    fh = masks(spec, **{"layers/wq": np.arange(L) < 4})
    plan = segment_plan(fh, spec, L, segment_max=4)
    per_row = params["layers"]["wq"].size // L
    assert plan_skipped_params(plan, params["layers"], L) == 4 * per_row
    assert plan_skipped_params(None, params["layers"], L) == 0
    # half-frozen everything: skip == half the monitored pool (the §8 check)
    fh = {n: (np.arange(L) < 4) if m.ndim == 1 else
          np.repeat((np.arange(L) < 4)[:, None], E, axis=1)
          for n, m in masks(spec).items()}
    plan = segment_plan(fh, spec, L, segment_max=4)
    pool = sum(params["layers"][k].size for k in ("wq", "w_up", "w_gate"))
    assert plan_skipped_params(plan, params["layers"], L) == pool // 2


def _assert_same_tree(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert type(x) is type(y)
        np.testing.assert_array_equal(x, y)


def _gate_rows(rows):
    gate = np.zeros((L, E), bool)
    gate[rows] = True
    return gate


@pytest.mark.parametrize("masks_of, segments", [
    # per-layer only: wq's lower half and two whole expert rows
    (lambda: {"layers/wq": np.arange(L) < 4,
              "layers/w_gate": _gate_rows(slice(0, 2))}, True),
    # whole type plus per-layer (the ft-wave shape): wq everywhere, the MLP
    # in the lower layers
    (lambda: {"layers/wq": np.ones(L, bool),
              "layers/w_up": np.arange(L) < 4,
              "layers/w_gate": _gate_rows(slice(0, 4))}, True),
    # a family whose forward consumes no segment plan
    (lambda: {"layers/wq": np.ones(L, bool),
              "layers/w_up": np.arange(L) < 4}, False),
], ids=["per_layer", "whole_and_per_layer", "no_segment_plan"])
def test_freeze_plan_matches_its_parts(masks_of, segments):
    """``freeze_plan`` is exactly the four derivations, field by field, and
    two plans that differ only in freezes the quantized grid ignores compare
    (and hash) equal, with the same rows, reduce plan and moment layout."""
    params = make_params()
    spec = build_monitor_spec(params)
    seg_max = 4     # cells of two layers
    fh = masks(spec, **masks_of())
    fz = freeze_plan(fh, params, spec, L, seg_max, segments=segments)
    whole = fully_frozen_types(fh)
    plan = segment_plan(fh, spec, L, seg_max) if segments else None
    rows = plan_row_masks(plan, spec, fh)
    assert fz.whole == whole
    assert fz.segments == plan
    assert (plan is not None and not plan.trivial) == segments
    if rows is None:
        assert fz.rows is None
    else:
        assert fz.rows.keys() == rows.keys()
        for name in rows:
            np.testing.assert_array_equal(fz.rows[name], rows[name])
    assert fz.reduce == gradient_reduce_plan(spec, whole, plan, L)
    _assert_same_tree(fz.trainable, trainable_mask(params, spec, whole, rows))
    # freezes inside a cell the wavefront has not completed: w_up at layer 5
    # alone (cell [4, 6)) and one expert of layer 6
    fh2 = dict(fh, **{"layers/w_up": fh["layers/w_up"] | (np.arange(L) == 5)})
    fh2["layers/w_gate"] = fh["layers/w_gate"].copy()
    fh2["layers/w_gate"][6, 0] = True
    fz2 = freeze_plan(fh2, params, spec, L, seg_max, segments=segments)
    assert fz2 == fz and hash(fz2) == hash(fz)
    assert fz2.reduce == fz.reduce
    _assert_same_tree(fz2.rows, fz.rows)
    _assert_same_tree(fz2.trainable, fz.trainable)
    # a completed cell is not ignored
    fh3 = dict(fh2)
    fh3["layers/w_up"] = fh2["layers/w_up"] | (np.arange(L) == 4)
    assert (freeze_plan(fh3, params, spec, L, seg_max, segments=segments)
            != fz) == segments
