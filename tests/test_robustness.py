"""Chaos-hardening units (DESIGN.md §4): deterministic fault plans, the
numerics guard's boundary rollback, self-healing checkpoints, Prefetcher
retry/stall behaviour, and the straggler watchdog escalation.

Everything here is fast and in-process — the subprocess kill/SIGTERM matrix
lives in ``test_chaos.py`` (slow + chaos markers)."""
import dataclasses
import math
import os
import shutil
import tempfile
import threading
import time

import jax
import numpy as np
import pytest

import repro.configs as configs
from repro.checkpoint.manager import CheckpointManager
from repro.config import GradESConfig, TrainConfig
from repro.data.pipeline import PrefetchStalled, Prefetcher, make_batches
from repro.robustness.faults import (CORRUPT_MODES, EXIT_NONFINITE,
                                     EXIT_PREEMPTED, EXIT_STRAGGLER,
                                     FaultPlan, FaultSpec, FaultyBatchSource,
                                     corrupt_checkpoint, exit_code_for)
from repro.train.loop import (Trainer, _ChainedSource, _live_ranges,
                              _plan_blocks)

CFG = configs.reduced("qwen3-0.6b")


def _tcfg(**kw):
    base = dict(seq_len=32, global_batch=4, steps=16, lr=3e-3, sync_interval=4,
                grades=GradESConfig(enabled=False))
    base.update(kw)
    return TrainConfig(**base)


def _assert_trees_equal(a, b, what=""):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=what)


# ------------------------------------------------------------- fault plans

def test_fault_plan_parse_and_purity():
    plan = FaultPlan.parse(["nan_grad@10:2.0", "inf_grad@11", "kill@20",
                            "sigterm@30", "ckpt_corrupt@16:truncate",
                            "io_error@5:2", "straggler@9:0.5"], seed=3)
    # grad gains: scale×NaN / ×Inf at the planned step, exactly 1.0 elsewhere
    assert math.isnan(plan.grad_gain(10))
    assert plan.grad_gain(11) == float("inf")
    assert plan.grad_gain(9) == 1.0 and plan.grad_gain(12) == 1.0
    assert plan.has_grad_faults and plan.has_io_faults
    # signals key on the dispatched block's [start, end) range
    assert plan.signal_in(16, 24) == "kill"
    assert plan.signal_in(28, 32) == "sigterm"
    assert plan.signal_in(0, 16) is None
    assert plan.io_failures(5) == 2 and plan.io_failures(6) == 0
    assert plan.straggler_delay(8, 4) == 0.5
    assert plan.straggler_delay(12, 4) == 0.0
    assert plan.corrupt_mode(16) == "truncate"
    assert plan.corrupt_mode(8) is None
    # every choice is pure in (seed, step): re-parsing gives the same answers
    again = FaultPlan.parse(["nan_grad@10:2.0"], seed=3)
    assert again.grad_target_index(7) == plan.grad_target_index(7) == 3 % 7
    assert plan == FaultPlan.parse(
        ["nan_grad@10:2.0", "inf_grad@11", "kill@20", "sigterm@30",
         "ckpt_corrupt@16:truncate", "io_error@5:2", "straggler@9:0.5"],
        seed=3)


def test_fault_plan_rejects_garbage():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec(kind="meteor", step=3)
    with pytest.raises(ValueError, match="kind@step"):
        FaultPlan.parse(["nan_grad"])
    with pytest.raises(ValueError, match="corrupt mode"):
        FaultPlan.parse(["ckpt_corrupt@8:gamma_ray"]).corrupt_mode(8)


def test_exit_codes_map_stop_reasons():
    assert exit_code_for("budget") == 0
    assert exit_code_for("all_frozen") == 0
    assert exit_code_for("val_es") == 0
    assert exit_code_for("preempted") == EXIT_PREEMPTED == 75
    assert exit_code_for("straggler_abort") == EXIT_STRAGGLER == 76
    assert exit_code_for("nonfinite_abort") == EXIT_NONFINITE == 77


# ------------------------------------------------- rollback range planning

def test_live_ranges_subtract_skips():
    assert _live_ranges(0, 24, []) == [(0, 24)]
    assert _live_ranges(0, 24, [(8, 12)]) == [(0, 8), (12, 24)]
    assert _live_ranges(8, 24, [(8, 12)]) == [(12, 24)]
    assert _live_ranges(0, 24, [(8, 12), (12, 16)]) == [(0, 8), (16, 24)]
    assert _live_ranges(0, 24, [(20, 28)]) == [(0, 20)]
    assert _live_ranges(12, 24, [(0, 4)]) == [(12, 24)]  # stale skip ignored
    assert _live_ranges(0, 8, [(0, 8)]) == []


def test_plan_blocks_schedules_each_range_on_grid():
    # a resumed range realigns onto the K-grid before full blocks
    assert _plan_blocks([(0, 8), (12, 24)], 8) == [(0, 8), (12, 4), (16, 8)]
    assert _plan_blocks([(0, 10)], 4) == [(0, 4), (4, 4), (8, 2)]
    assert _plan_blocks([], 4) == []
    # block starts tile the live steps exactly
    for ranges in ([(0, 24)], [(0, 6), (10, 24)]):
        covered = [s for start, sz in _plan_blocks(ranges, 4)
                   for s in range(start, start + sz)]
        want = [s for lo, hi in ranges for s in range(lo, hi)]
        assert covered == want


def test_chained_source_survives_exceptions():
    """An exception from the active range must propagate to the consumer but
    leave the chain usable — the retrying consumer resumes the same stream
    (a generator/itertools.chain would be dead after the first raise)."""
    class Flaky:
        def __init__(self, items, fail_at):
            self._it = iter(items)
            self._fail = fail_at

        def __iter__(self):
            return self

        def __next__(self):
            if self._fail > 0:
                self._fail -= 1
                raise OSError("transient")
            return next(self._it)

    src = _ChainedSource([lambda: Flaky([0, 1], fail_at=0),
                          lambda: Flaky([2, 3], fail_at=2),
                          lambda: iter([4])])
    got = []
    while True:
        try:
            got.append(next(src))
        except OSError:
            continue
        except StopIteration:
            break
    assert got == [0, 1, 2, 3, 4]


# --------------------------------------------------------- injected I/O

def test_faulty_batch_source_is_retry_safe():
    """The injected OSError fires *before* the source advances, so a retrying
    consumer loses no batch and duplicates none."""
    plan = FaultPlan.parse(["io_error@2:2"])
    src = FaultyBatchSource(iter(range(5)), plan)
    got, raises = [], 0
    while True:
        try:
            got.append(next(src))
        except OSError:
            raises += 1
        except StopIteration:
            break
    assert got == [0, 1, 2, 3, 4]
    assert raises == 2


def test_prefetcher_transient_io_is_loss_free():
    tcfg = _tcfg()
    plan = FaultPlan.parse(["io_error@3:2"])
    clean = list(Prefetcher(make_batches(CFG, tcfg, steps=8), [4, 4], depth=2))
    faulty = list(Prefetcher(
        FaultyBatchSource(make_batches(CFG, tcfg, steps=8), plan),
        [4, 4], depth=2, retries=3, retry_backoff=0.0))
    assert len(faulty) == len(clean) == 2
    for a, b in zip(clean, faulty):
        _assert_trees_equal(a, b, "retried stream diverged")


@pytest.mark.parametrize("depth", [0, 2])
def test_prefetcher_persistent_io_reraises_original(depth):
    tcfg = _tcfg()
    plan = FaultPlan.parse(["io_error@2:10"])  # outlasts the retry budget
    pf = Prefetcher(FaultyBatchSource(make_batches(CFG, tcfg, steps=8), plan),
                    [4, 4], depth=depth, retries=2, retry_backoff=0.0)
    with pytest.raises(OSError, match="injected I/O error reading batch 2"):
        for _ in range(3):
            next(pf)
    pf.close()


def test_prefetcher_stall_timeout_and_leak_flag():
    """A wedged source raises PrefetchStalled instead of hanging the trainer,
    and close() flags (not hides) the worker it could not join."""
    release = threading.Event()

    def wedged():
        yield {"x": np.zeros(1)}
        release.wait()  # simulates a hung filesystem read
        yield {"x": np.ones(1)}

    pf = Prefetcher(wedged(), [1, 1], depth=1, stall_timeout=0.2)
    assert next(pf) is not None
    with pytest.raises(PrefetchStalled, match="no block within"):
        next(pf)
    t0 = time.perf_counter()
    pf.close()  # join times out; must return with the leak made visible
    assert time.perf_counter() - t0 < 30.0
    assert pf.leaked_thread
    release.set()
    pf._thread.join(timeout=5.0)
    assert not pf._thread.is_alive()


def test_prefetcher_clean_close_does_not_flag_leak():
    tcfg = _tcfg()
    pf = Prefetcher(make_batches(CFG, tcfg, steps=8), [4, 4], depth=2)
    next(pf)
    pf.close()
    assert not pf.leaked_thread


# ------------------------------------------- self-healing checkpoint store

def _tree(step):
    rng = np.random.default_rng(step)
    return {"w": rng.standard_normal((4, 3)).astype(np.float32),
            "opt": {"m": rng.standard_normal(5).astype(np.float32),
                    "count": np.int32(step)}}


@pytest.mark.parametrize("mode", CORRUPT_MODES)
@pytest.mark.parametrize("target", ["newest", "older"])
def test_corruption_matrix_restores_newest_valid(mode, target):
    """bitflip / truncate / delete_leaf × newest / older step: verify()
    catches every mode, latest_valid() lands on the newest intact step and
    quarantines only what it had to walk past."""
    d = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(d, keep=5)
        for s in (8, 16, 24):
            mgr.save(s, _tree(s), blocking=True)
        victim = 24 if target == "newest" else 16
        corrupt_checkpoint(d, victim, mode, seed=0)
        assert not mgr.verify(victim), (mode, target)
        for s in (8, 16, 24):
            if s != victim:
                assert mgr.verify(s), (mode, target, s)
        got = mgr.latest_valid()
        if target == "newest":
            # the damaged head is quarantined and restore falls back one step
            assert got == 16
            assert os.path.isdir(os.path.join(d, "step_24.corrupt"))
            assert not os.path.exists(os.path.join(d, "step_24"))
        else:
            # damage below the head is invisible to restore (never walked)
            assert got == 24
        restored = mgr.restore(got, _tree(0))
        _assert_trees_equal(restored, _tree(got), f"{mode}/{target}")
    finally:
        shutil.rmtree(d)


def test_missing_manifest_is_not_a_step():
    d = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(d, keep=5)
        mgr.save(8, _tree(8), blocking=True)
        os.makedirs(os.path.join(d, "step_16"))  # torn dir, no manifest
        assert mgr.steps() == [8]
        assert mgr.latest_valid() == 8
    finally:
        shutil.rmtree(d)


def test_quarantined_steps_stay_invisible():
    d = tempfile.mkdtemp()
    try:
        mgr = CheckpointManager(d, keep=5)
        for s in (8, 16):
            mgr.save(s, _tree(s), blocking=True)
        corrupt_checkpoint(d, 16, "truncate", seed=0)
        assert mgr.latest_valid() == 8
        # the .corrupt dir is neither a step nor re-quarantined on re-walk
        assert mgr.steps() == [8]
        assert mgr.latest_valid() == 8
        # and a revisited boundary can overwrite the quarantined step's slot
        mgr.save(16, _tree(16), blocking=True)
        assert mgr.latest_valid() == 16
    finally:
        shutil.rmtree(d)


def test_latest_valid_under_concurrent_writers():
    """Several writers saving interleaved steps into ONE directory (an elastic
    fleet's old and relaunched chief overlapping at a drain) — the atomic-
    rename invariant, asserted directly *while the race runs*: any step a
    reader can see (manifest present) is complete and CRC-valid, because a
    step only ever appears via rename of a fully-fsynced staging dir."""
    d = tempfile.mkdtemp()
    try:
        all_steps = list(range(1, 25))
        writers = [CheckpointManager(d, keep=100) for _ in range(3)]
        threads = [threading.Thread(
            target=lambda m=m, i=i: [m.save(s, _tree(s), blocking=True)
                                     for s in all_steps[i::3]])
            for i, m in enumerate(writers)]
        reader = CheckpointManager(d, keep=100)
        for t in threads:
            t.start()
        while any(t.is_alive() for t in threads):
            for s in reader.steps():      # visible ⇒ verifiable, mid-race
                assert reader.verify(s), f"step_{s} visible but torn"
        for t in threads:
            t.join()
        # every step landed intact; latest_valid walks cleanly to the head
        assert reader.steps() == all_steps
        assert reader.latest_valid() == 24
        assert not [f for f in os.listdir(d) if ".tmp" in f], "staging leaked"
        _assert_trees_equal(reader.restore(24, _tree(0)), _tree(24))
    finally:
        shutil.rmtree(d)


def test_same_step_writer_race_is_bit_safe():
    """Two managers racing the SAME boundary step (restart overlap): unique
    per-writer staging dirs mean neither tears the other; whichever writer
    wins, the published step verifies and restores to the boundary state."""
    d = tempfile.mkdtemp()
    try:
        mgrs = [CheckpointManager(d, keep=5) for _ in range(2)]
        for _ in range(10):  # many rounds to actually interleave the rename
            threads = [threading.Thread(
                target=m.save, args=(8, _tree(8)),
                kwargs={"blocking": True}) for m in mgrs]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert mgrs[0].verify(8)
        assert mgrs[0].latest_valid() == 8
        _assert_trees_equal(mgrs[0].restore(8, _tree(0)), _tree(8))
        assert not [f for f in os.listdir(d) if ".tmp" in f], "staging leaked"
    finally:
        shutil.rmtree(d)


# ------------------------------------------------- numerics-guard rollback

@pytest.fixture(scope="module")
def rollback_run():
    """One NaN-tripped run with the default step-keyed stream — the reference
    the determinism and callable-source tests both compare against."""
    tcfg = _tcfg(fault_plan=FaultPlan.parse(["nan_grad@6"]))
    return tcfg, Trainer(CFG, tcfg, log_every=4).train()


def test_rollback_replay_is_deterministic(rollback_run):
    """A guard trip rolls back to the boundary snapshot, skips the poisoned
    block, backs off the LR — and because faults and data are both step-keyed,
    the whole recovery replays bit-identically."""
    tcfg, r1 = rollback_run
    r2 = Trainer(CFG, tcfg, log_every=4).train()
    for r in (r1, r2):
        assert r.stop_reason == "budget"
        assert r.rollbacks == 1
        assert r.steps_run == tcfg.steps - tcfg.sync_interval  # block skipped
    _assert_trees_equal(r1.state.params, r2.state.params, "params")
    _assert_trees_equal(r1.state.opt, r2.state.opt, "opt")
    rb = [h for h in r1.history if "rollback" in h]
    assert len(rb) == 1
    assert rb[0]["step"] == 4.0  # the block [4, 8) containing step 6
    assert rb[0]["lr_scale"] == tcfg.rollback_lr_backoff
    # the healthy prefix is bit-identical to a fault-free run (the ×1.0
    # fault_gain tag is a numeric no-op), so the divergence is only the
    # documented skip + backoff
    r0 = Trainer(CFG, _tcfg(), log_every=4).train()
    l0 = {h["step"]: h["loss"] for h in r0.history}
    for h in r1.history:
        if "loss" in h and h["step"] < 4:
            assert l0[h["step"]] == h["loss"]


def test_rollback_restores_packed_moments_bit_identically():
    """A trip after a repartition boundary, with Tier-1.5 row-packed moments
    live: the MLP of layer 0 is frozen from the start (so its moments are
    packed to the live rows), and "layers/wq" freezes whole at once, so the
    boundary at step 4 re-plans it to a placeholder.  The snapshot taken
    there holds that packed layout; the rollback restores it without
    re-packing and the whole recovery replays bit for bit."""
    tcfg = _tcfg(fault_plan=FaultPlan.parse(["nan_grad@6"]),
                 grades=GradESConfig(enabled=True, alpha=0.0, tau=1e-9,
                                     tau_overrides={"layers/wq": 1e9}))

    def run():
        trainer = Trainer(CFG, tcfg, repartition_interval=4, log_every=4)
        state = trainer.init_state()
        frozen = dict(state.grades.frozen)
        for name in ("layers/w_gate", "layers/w_up", "layers/w_down"):
            frozen[name] = frozen[name].at[0].set(True)
        state.grades.frozen = frozen
        return trainer.train(state=state)

    r1, r2 = run(), run()
    for r in (r1, r2):
        assert r.stop_reason == "budget"
        assert r.rollbacks == 1
        assert r.steps_run == tcfg.steps - tcfg.sync_interval
        assert [h["step"] for h in r.history if "rollback" in h] == [4.0]
    m, p = r1.state.opt.m["layers"], r1.state.params["layers"]
    assert m["w_up"].shape == (1,) + p["w_up"].shape[1:]   # packed rows
    assert m["wq"].shape == (1,)                         # placeholder
    _assert_trees_equal(r1.state.params, r2.state.params, "params")
    _assert_trees_equal(r1.state.opt, r2.state.opt, "opt")
    _assert_trees_equal(r1.state.grades, r2.state.grades, "grades")


def test_rollback_budget_exhausted_aborts():
    plan = FaultPlan.parse(["nan_grad@6"])
    res = Trainer(CFG, _tcfg(fault_plan=plan, max_rollbacks=0),
                  log_every=4).train()
    assert res.stop_reason == "nonfinite_abort"
    assert res.rollbacks == 0
    assert exit_code_for(res.stop_reason) == EXIT_NONFINITE


def test_bare_iterator_cannot_replay_so_trips_abort():
    """A caller-owned iterator has no step-keyed replay, so the guard must
    abort resumable instead of silently rolling back into replayed data."""
    plan = FaultPlan.parse(["nan_grad@6"])
    tcfg = _tcfg(fault_plan=plan)
    res = Trainer(CFG, tcfg, log_every=4).train(
        batches=make_batches(CFG, tcfg, steps=16))
    assert res.stop_reason == "nonfinite_abort"
    assert res.rollbacks == 0


def test_guard_off_trains_through_nonfinite():
    plan = FaultPlan.parse(["nan_grad@6"])
    res = Trainer(CFG, _tcfg(fault_plan=plan, numerics_guard=False),
                  log_every=4).train()
    assert res.stop_reason == "budget"
    assert res.rollbacks == 0
    assert res.steps_run == 16  # nothing skipped; NaNs propagate (documented)


def test_callable_source_supports_rollback(rollback_run):
    """The callable-batches protocol (external seekable datasets) replays from
    an arbitrary step, so the guard rolls back instead of aborting."""
    tcfg, ref = rollback_run

    def source(start):
        return make_batches(CFG, tcfg, start_step=start,
                            steps=tcfg.steps - start)

    res = Trainer(CFG, tcfg, log_every=4).train(batches=source)
    assert res.stop_reason == "budget"
    assert res.rollbacks == 1
    # identical to the default step-keyed stream's recovery
    _assert_trees_equal(res.state.params, ref.state.params, "params")


# --------------------------------------------------- straggler escalation

def test_straggler_escalation_checkpoints_and_aborts():
    d = tempfile.mkdtemp()
    try:
        plan = FaultPlan.parse(["straggler@9:2.0"])
        tcfg = _tcfg(steps=24, fault_plan=plan, straggler_p95_abort=3.0,
                     checkpoint_dir=d)
        res = Trainer(CFG, tcfg, log_every=4).train()
        assert res.stop_reason == "straggler_abort"
        assert exit_code_for(res.stop_reason) == EXIT_STRAGGLER
        assert res.steps_run < 24
        # the escalation wrote a boundary checkpoint a relaunch resumes from
        mgr = CheckpointManager(d)
        latest = mgr.latest_valid()
        assert latest is not None and latest % tcfg.sync_interval == 0
        resumed = Trainer(CFG, dataclasses.replace(
            tcfg, fault_plan=None, straggler_p95_abort=0.0),
            log_every=4).train()
        assert resumed.stop_reason == "budget"
        assert resumed.steps_run == 24 - latest
    finally:
        shutil.rmtree(d)


def test_straggler_log_only_by_default():
    plan = FaultPlan.parse(["straggler@9:0.3"])
    res = Trainer(CFG, _tcfg(fault_plan=plan), log_every=4).train()
    assert res.stop_reason == "budget"
    assert res.steps_run == 16


# ------------------------------------------------------- graceful shutdown

def test_graceful_shutdown_catches_sigterm():
    import signal
    from repro.robustness.harness import GracefulShutdown
    gs = GracefulShutdown()
    try:
        assert not gs.requested
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.05)  # let the interpreter run the Python-level handler
        assert gs.requested
    finally:
        gs.uninstall()
    # uninstalled: the previous (default) disposition is back
    assert signal.getsignal(signal.SIGTERM) != gs._handler


def test_graceful_shutdown_request_without_signal():
    from repro.robustness.harness import GracefulShutdown
    with GracefulShutdown(install=False) as gs:
        assert not gs.requested
        gs.request()
        assert gs.requested


def test_graceful_shutdown_sigint_drains_then_second_reraises():
    """First SIGINT = drain request (no KeyboardInterrupt); a second SIGINT
    while draining restores the previous handler and re-raises through it —
    and only SIGINT's shield drops, the SIGTERM one stays up."""
    import signal
    from repro.robustness.harness import GracefulShutdown
    prev_int = signal.getsignal(signal.SIGINT)
    gs = GracefulShutdown()
    try:
        os.kill(os.getpid(), signal.SIGINT)
        time.sleep(0.05)
        assert gs.requested  # drained, not killed
        with pytest.raises(KeyboardInterrupt):
            os.kill(os.getpid(), signal.SIGINT)
            time.sleep(0.05)
        # the re-raise path restored the previous SIGINT disposition...
        assert signal.getsignal(signal.SIGINT) == prev_int
        # ...while SIGTERM is still shielded by the drain handler
        assert signal.getsignal(signal.SIGTERM) == gs._handler
    finally:
        gs.uninstall()
    assert signal.getsignal(signal.SIGTERM) != gs._handler


def test_graceful_shutdown_handles_both_drain_signals():
    import signal
    from repro.robustness.harness import GracefulShutdown
    with GracefulShutdown() as gs:
        assert signal.getsignal(signal.SIGTERM) == gs._handler
        assert signal.getsignal(signal.SIGINT) == gs._handler
    assert signal.getsignal(signal.SIGTERM) != gs._handler
    assert signal.getsignal(signal.SIGINT) != gs._handler
