"""Sync-boundary trainer semantics (DESIGN.md §4).

The contract under test: the host-side block granularity is *invisible* to the
math — ``sync_interval=K`` produces bit-identical params / optimizer / frozen
masks to ``K=1`` across Tier-1 repartitions and Tier-2 termination, a resumed
run continues the step-indexed data stream (no batch replay), and the history
always records the terminal step.
"""
import dataclasses
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.core.partition import SegmentPlan, freeze_plan, segment_plan
from repro.data.pipeline import (PackedFileDataset, Prefetcher, make_batches,
                                 stack_batches)
from repro.models import model
from repro.train.loop import Trainer, block_schedule
from repro.train.state import init_train_state
from repro.train.step import make_multi_step, make_train_step

CFG = configs.reduced("qwen3-0.6b")


def _tcfg(**kw):
    base = dict(seq_len=32, global_batch=8, steps=24, lr=3e-3,
                grades=GradESConfig(enabled=False))
    base.update(kw)
    return TrainConfig(**base)


def _assert_trees_equal(a, b, what=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


# ---------------------------------------------------------------- scheduling

def test_block_schedule_covers_budget():
    assert block_schedule(0, 20, 8) == [8, 8, 4]
    assert block_schedule(3, 20, 8) == [5, 8, 4]   # re-align, then K-grid
    assert block_schedule(16, 16, 8) == []
    assert block_schedule(0, 5, 8) == [5]
    assert block_schedule(0, 24, 1) == [1] * 24
    for start, total, k in ((0, 20, 8), (3, 20, 8), (7, 100, 16)):
        assert sum(block_schedule(start, total, k)) == total - start


# ------------------------------------------------------- multi-step parity

def test_multi_step_matches_single_steps():
    tcfg = _tcfg(grades=GradESConfig(enabled=True, tau=4e-3, alpha=0.3,
                                     normalize=True))
    state_a = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    state_b = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    spec = build_monitor_spec(state_a.params)
    single = jax.jit(make_train_step(CFG, tcfg, spec))
    multi = jax.jit(make_multi_step(CFG, tcfg, spec))
    batches = list(make_batches(CFG, tcfg, steps=4))
    for b in batches:
        state_a, m_single = single(state_a, b)
    block = jax.device_put(stack_batches(batches))
    state_b, m_block = multi(state_b, block)
    _assert_trees_equal(state_a.params, state_b.params, "params")
    _assert_trees_equal(state_a.opt, state_b.opt, "opt")
    _assert_trees_equal(state_a.grades.frozen, state_b.grades.frozen, "frozen")
    # stacked (K,) metrics, final row matches the sequential last step
    assert m_block["loss"].shape == (4,)
    np.testing.assert_array_equal(np.asarray(m_block["loss"][-1]),
                                  np.asarray(m_single["loss"]))
    assert float(m_block["executed"].sum()) == 4.0


def test_sync_interval_bit_identical_across_tier1():
    """K=8 vs K=1 over a run whose per-layer freeze wavefront crosses a
    Tier-1/1.5 plan change at an aligned boundary (the acceptance criterion):
    params/opt/frozen bit-identical, same recompiles — and the Tier-1.5
    artifacts are real: per-row packed moments and the documented recompile
    bound."""
    tcfg = _tcfg(steps=48, grades=GradESConfig(
        enabled=True, tau=6e-3, alpha=0.2, normalize=True, patience=1))
    r1 = Trainer(CFG, tcfg, repartition_interval=16, log_every=10).train()
    r8 = Trainer(CFG, dataclasses.replace(tcfg, sync_interval=8),
                 repartition_interval=16, log_every=10).train()
    assert r1.recompiles >= 1, "test needs a plan change to fire"
    assert r8.recompiles == r1.recompiles
    assert r8.steps_run == r1.steps_run == 48
    _assert_trees_equal(r1.state.params, r8.state.params, "params")
    _assert_trees_equal(r1.state.opt, r8.state.opt, "opt")
    _assert_trees_equal(r1.state.grades.frozen, r8.state.grades.frozen,
                        "frozen")
    # logged metric rows agree step-for-step on the device-computed values
    l1 = {h["step"]: h["loss"] for h in r1.history}
    l8 = {h["step"]: h["loss"] for h in r8.history}
    assert set(l1) == set(l8)
    assert all(l1[s] == l8[s] for s in l1)
    # Tier-1.5: recompiles within the segment_max * n_types bound, and some
    # monitored leaf's moments are row-packed (memory freed before any whole
    # type converged; packing reflects the last boundary's masks)
    spec = build_monitor_spec(r1.state.params)
    assert r1.recompiles <= tcfg.segment_max * len(spec.groups)
    frozen = {n: np.asarray(m) for n, m in
              jax.device_get(r1.state.grades.frozen).items()}
    assert any(0 < m.sum() < m.size for m in frozen.values()), \
        "wavefront never partially froze a type; retune tau"
    packed = []
    for name in spec.groups:
        path = spec.groups[name][0][0]
        m_leaf = r1.state.opt.m[path[0]][path[1]]
        p_leaf = r1.state.params[path[0]][path[1]]
        if m_leaf.size > 1 and m_leaf.shape != p_leaf.shape:
            assert 0 < m_leaf.shape[0] < p_leaf.shape[0], (name, m_leaf.shape)
            packed.append(name)
    assert packed, "no moment buffer was row-packed"


def test_tier2_terminates_identically_mid_block():
    """All-frozen lands mid-block: the in-scan gate must stop the state at
    exactly the K=1 stopping point (trailing steps are no-ops)."""
    tcfg = _tcfg(steps=300, grades=GradESConfig(
        enabled=True, tau=1e3, alpha=0.1, normalize=True, patience=1))
    r1 = Trainer(CFG, tcfg, log_every=10).train()
    r8 = Trainer(CFG, dataclasses.replace(tcfg, sync_interval=8),
                 log_every=10).train()
    assert r1.stop_reason == r8.stop_reason == "all_frozen"
    assert r8.steps_run == r1.steps_run
    _assert_trees_equal(r1.state.params, r8.state.params, "params")
    _assert_trees_equal(r1.state.opt, r8.state.opt, "opt")
    # unmonitored params (embeddings) must NOT keep training past the stop
    _assert_trees_equal(r1.state.params["embed"], r8.state.params["embed"],
                        "embed")


# ------------------------------------------- Tier 1.5: segmented layer scan

def test_segmented_step_bit_identical_to_monolithic():
    """Segmentation alone (empty signatures) is invisible: the chain of
    segment scans produces bit-identical params/opt/frozen/metrics to the
    single monolithic scan."""
    tcfg = _tcfg(steps=8, grades=GradESConfig(enabled=True, tau=4e-3,
                                              alpha=0.3, normalize=True))
    L = CFG.n_layers
    plan = SegmentPlan(segments=tuple(
        (lo, min(lo + 1, L), frozenset()) for lo in range(L)))
    state_a = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    state_b = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    spec = build_monitor_spec(state_a.params)
    none = freeze_plan({}, state_a.params, spec, L, tcfg.segment_max,
                       segments=False, pack_rows=False)
    mono = jax.jit(make_train_step(CFG, tcfg, spec))
    segd = jax.jit(make_train_step(CFG, tcfg, spec,
                                   dataclasses.replace(none, segments=plan)))
    for b in make_batches(CFG, tcfg, steps=3):
        state_a, m_a = mono(state_a, b)
        state_b, m_b = segd(state_b, b)
    _assert_trees_equal(state_a.params, state_b.params, "params")
    _assert_trees_equal(state_a.opt, state_b.opt, "opt")
    _assert_trees_equal(state_a.grades.frozen, state_b.grades.frozen, "frozen")
    _assert_trees_equal(m_a, m_b, "metrics")


def test_segment_skip_grads_equal_zeroed_rows():
    """A segment signature's stop_gradient is exactly 'zero those rows' dW:
    surviving gradients are bit-identical to the planless backward, skipped
    rows are exactly zero (forward values unchanged)."""
    tcfg = _tcfg()
    state = init_train_state(jax.random.PRNGKey(1), CFG, tcfg)
    spec = build_monitor_spec(state.params)
    batch = next(iter(make_batches(CFG, tcfg, steps=1)))
    L = CFG.n_layers
    frozen = {n: np.arange(L) < L // 2 for n in spec.groups}
    plan = segment_plan(frozen, spec, L, segment_max=L)
    assert any(sig for _, _, sig in plan.segments)

    def loss(p, plan_):
        return model.loss_fn(p, batch, CFG, plan=plan_)[0]

    g_plan = jax.jit(jax.grad(loss), static_argnums=1)(state.params, plan)
    g_none = jax.jit(jax.grad(loss), static_argnums=1)(state.params, None)
    np.testing.assert_array_equal(
        np.asarray(loss(state.params, plan)),
        np.asarray(loss(state.params, None)))
    for name in spec.groups:
        path = spec.groups[name][0][0]
        leaf_p = np.asarray(g_plan[path[0]][path[1]])
        leaf_n = np.asarray(g_none[path[0]][path[1]])
        rows = np.asarray(frozen[name])
        assert (leaf_p[rows] == 0.0).all(), name
        np.testing.assert_array_equal(leaf_p[~rows], leaf_n[~rows],
                                      err_msg=name)
    # unmonitored params' grads are untouched by the plan
    np.testing.assert_array_equal(np.asarray(g_plan["embed"]),
                                  np.asarray(g_none["embed"]))


# --------------------------------------------------------- resume semantics

def test_resume_matches_uninterrupted():
    """Crash after the mid-run checkpoint: the resumed run must continue the
    step-indexed batch stream (no replay) and land bit-identically on the
    uninterrupted run, with matching loss curves over the resumed segment."""
    d = tempfile.mkdtemp()
    try:
        tcfg = _tcfg(steps=32, sync_interval=4, checkpoint_dir=d,
                     checkpoint_every=16, keep_checkpoints=5,
                     grades=GradESConfig(enabled=True, tau=4e-3, alpha=0.3,
                                         normalize=True))
        r_a = Trainer(CFG, tcfg, repartition_interval=16, log_every=1).train()
        assert sorted(os.listdir(d)) == ["step_16", "step_32"]
        shutil.rmtree(os.path.join(d, "step_32"))  # simulate a crash at 16
        r_b = Trainer(CFG, tcfg, repartition_interval=16, log_every=1).train()
        assert r_b.steps_run == 16  # resumed from the boundary, not step 0
        assert r_b.history[0]["step"] == 16
        _assert_trees_equal(r_a.state.params, r_b.state.params, "params")
        _assert_trees_equal(r_a.state.opt, r_b.state.opt, "opt")
        _assert_trees_equal(r_a.state.grades.frozen, r_b.state.grades.frozen,
                            "frozen")
        la = {h["step"]: h["loss"] for h in r_a.history}
        for h in r_b.history:
            assert la[h["step"]] == h["loss"], h["step"]
    finally:
        shutil.rmtree(d)


def test_resume_across_segment_max_change():
    """Checkpoints carry the plan-independent moment layout: a run saved with
    per-row packed moments under one segment_max restores under another —
    re-packed to the restoring run's own plan instead of erroring on layout
    provenance."""
    d = tempfile.mkdtemp()
    try:
        tcfg = _tcfg(steps=32, sync_interval=4, checkpoint_dir=d,
                     checkpoint_every=16, keep_checkpoints=5,
                     grades=GradESConfig(enabled=True, tau=6e-3, alpha=0.2,
                                         normalize=True, patience=1))
        r_a = Trainer(CFG, tcfg, repartition_interval=8, log_every=16).train()
        frozen = jax.device_get(r_a.state.grades.frozen)
        assert any(0 < np.asarray(m).sum() < np.asarray(m).size
                   for m in frozen.values()), "needs a partial freeze"
        shutil.rmtree(os.path.join(d, "step_32"))
        # saved moments are full/placeholder (plan-independent), so any
        # later run can re-pack them under a different plan
        for seg_max in (1, 3):
            r_b = Trainer(CFG, dataclasses.replace(tcfg, segment_max=seg_max),
                          repartition_interval=8, log_every=16).train()
            assert r_b.steps_run == 16
            shutil.rmtree(os.path.join(d, "step_32"))  # re-crash for the next
    finally:
        shutil.rmtree(d)


def test_make_batches_keyed_by_absolute_step():
    tcfg = _tcfg()
    full = list(make_batches(CFG, tcfg, steps=20))
    tail = list(make_batches(CFG, tcfg, steps=4, start_step=16))
    for a, b in zip(full[16:], tail):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])
    # default count respects the budget from the start offset
    assert len(list(make_batches(CFG, _tcfg(steps=10), start_step=7))) == 3


def test_packed_dataset_start_step_seeks():
    d = tempfile.mkdtemp()
    try:
        path = os.path.join(d, "packed.npy")
        rng = np.random.default_rng(0)
        PackedFileDataset.write(path, rng.integers(0, 64, (40, 17)))
        ds = PackedFileDataset(path, 16)
        full = [b for _, b in zip(range(25), ds.batches(4, seed=3))]
        tail = [b for _, b in zip(range(5), ds.batches(4, seed=3,
                                                       start_step=20))]
        for a, b in zip(full[20:], tail):
            np.testing.assert_array_equal(a["tokens"], b["tokens"])
            np.testing.assert_array_equal(a["labels"], b["labels"])
    finally:
        shutil.rmtree(d)


# ------------------------------------------------------------- prefetcher

@pytest.mark.parametrize("depth", [0, 2])
def test_prefetcher_matches_sync_stacking(depth):
    tcfg = _tcfg()
    sizes = [4, 4, 2]
    got = list(Prefetcher(make_batches(CFG, tcfg, steps=10), sizes,
                          depth=depth))
    want_batches = list(make_batches(CFG, tcfg, steps=10))
    assert [int(b["tokens"].shape[0]) for b in got] == sizes
    at = 0
    for block, size in zip(got, sizes):
        want = stack_batches(want_batches[at:at + size])
        for k in want:
            np.testing.assert_array_equal(np.asarray(block[k]), want[k])
        at += size


def test_prefetcher_short_source_and_close():
    tcfg = _tcfg()
    pf = Prefetcher(make_batches(CFG, tcfg, steps=5), [4, 4], depth=2)
    blocks = list(pf)
    # the short remainder is yielded, not dropped
    assert [int(b["tokens"].shape[0]) for b in blocks] == [4, 1]
    with pytest.raises(StopIteration):
        next(pf)  # exhausted iterators must not hang
    pf.close()  # idempotent
    # exceptions on the worker surface at the consumer
    def bad():
        yield from make_batches(CFG, tcfg, steps=1)
        raise RuntimeError("source died")
    pf = Prefetcher(bad(), [1, 1], depth=2)
    assert next(pf) is not None
    with pytest.raises(RuntimeError, match="source died"):
        for _ in range(4):
            next(pf)


def test_external_iterator_trains_every_batch():
    """A caller-supplied iterator that runs dry mid-block still has all its
    batches trained (the short remainder block is yielded, not dropped)."""
    tcfg = _tcfg(steps=16, sync_interval=8)
    res = Trainer(CFG, tcfg, log_every=100).train(
        batches=make_batches(CFG, tcfg, steps=10))
    assert res.steps_run == 10
    assert res.history[-1]["step"] == 9


# ------------------------------------------------------------ history fix

def test_history_always_records_terminal_step():
    # budget end between log points: 24 steps, log_every=10 -> 0, 10, 20, 23
    res = Trainer(CFG, _tcfg(steps=24), log_every=10).train()
    steps = [h["step"] for h in res.history]
    assert steps[-1] == 23 and steps[:-1] == [0, 10, 20]
    # val-ES break off the log cadence still records its terminal step
    val = list(make_batches(CFG, _tcfg(), steps=2, seed_offset=100))
    tcfg = _tcfg(steps=200, val_es=True, val_interval_frac=0.05,
                 val_patience=2, val_delta=1e9)
    res = Trainer(CFG, tcfg, log_every=50).train(val_batches=val)
    assert res.stop_reason == "val_es"
    assert res.history[-1]["step"] == res.steps_run - 1


def test_val_es_patience_accrues_per_crossed_multiple():
    """val_interval < K: a non-improving boundary eval accrues one patience
    count per crossed multiple (the K=1 plateau cadence), while an improving
    eval counts once — never one-count-per-boundary."""
    val = list(make_batches(CFG, _tcfg(), steps=2, seed_offset=100))
    tcfg = _tcfg(steps=200, sync_interval=32, val_es=True,
                 val_interval_frac=0.05, val_patience=2, val_delta=1e9)
    res = Trainer(CFG, tcfg, log_every=50).train(val_batches=val)
    assert res.stop_reason == "val_es"
    # boundary 32: first eval improves from inf (patience reset); boundary
    # 64: 3 crossed multiples on a plateau -> val_bad=3 >= 2 -> stop.  With
    # one-count-per-boundary accrual this would take 96 steps.
    assert res.steps_run == 64


def test_watchdog_block_timings_in_history():
    res = Trainer(CFG, _tcfg(steps=24, sync_interval=8), log_every=8).train()
    last = res.history[-1]
    assert "dt" in last and "dt_p50" in last and "dt_p95" in last
    assert last["dt_p95"] >= last["dt_p50"] > 0.0


def test_sigterm_drain_resume_bit_identical():
    """SIGTERM mid-run becomes a graceful drain (DESIGN.md §4): the in-flight
    block is settled, a boundary checkpoint is written synchronously, the run
    exits with stop_reason="preempted", and a relaunch resumes to a final
    state bit-identical to the uninterrupted run.  GradES is off here so the
    drain checkpoint's extra boundary cannot shift the freeze-artifact
    refresh schedule (with it on, runs are bit-comparable only when their
    checkpoint boundaries coincide — module docstring of train/loop.py)."""
    from repro.robustness.faults import FaultPlan
    d = tempfile.mkdtemp()
    try:
        base = _tcfg(steps=24, sync_interval=4)
        r_a = Trainer(CFG, base, log_every=8).train()  # uninterrupted
        tcfg = dataclasses.replace(
            base, checkpoint_dir=d,
            fault_plan=FaultPlan.parse(["sigterm@10"]))
        r_b = Trainer(CFG, tcfg, log_every=8).train()
        assert r_b.stop_reason == "preempted"
        assert 0 < r_b.steps_run < 24
        assert r_b.steps_run % 4 == 0  # drained to a sync boundary
        assert sorted(os.listdir(d)) == [f"step_{r_b.steps_run}"]
        r_c = Trainer(CFG, dataclasses.replace(base, checkpoint_dir=d),
                      log_every=8).train()
        assert r_c.steps_run == 24 - r_b.steps_run
        _assert_trees_equal(r_a.state.params, r_c.state.params, "params")
        _assert_trees_equal(r_a.state.opt, r_c.state.opt, "opt")
    finally:
        shutil.rmtree(d)
