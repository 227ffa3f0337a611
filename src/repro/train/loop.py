"""Host-side training controller: sync boundaries + the three GradES tiers.

The host only wakes at **sync boundaries** — every ``tcfg.sync_interval`` (K)
steps (DESIGN.md §4).  The compiled step is ``lax.scan``'d over a stacked
``(K, ...)`` batch block (``train/step.py::make_multi_step``); batch blocks are
sampled, stacked and ``jax.device_put`` on a background thread
(``data/pipeline.py::Prefetcher``), and per-step metrics come back in one bulk
``device_get`` per block, drained one block *behind* the dispatch so host-side
bookkeeping overlaps device execution:

* Tier 0 (in-jit freeze masks) lives in the compiled step.
* Tier 1 / 1.5: at boundaries aligned to ``round_up(repartition_interval,
  K)`` the host reads the (tiny) frozen masks and derives one
  :class:`~repro.core.partition.FreezePlan` from them
  (``partition.freeze_plan``); when it differs from the step's plan,
  ``repartition`` repacks the moments and error-feedback buffers to its
  layout and re-jits.  The plan is a pure function of the masks, so a
  resumed run re-derives it identically; recompiles are bounded at
  ``segment_max · n_types`` by the planner's grid quantization (DESIGN.md
  §2).  Runs with different ``sync_interval`` are bit-identical when they
  resolve to the same aligned interval (``repartition_interval`` a
  common multiple of the K values compared): the re-jit then lands on the
  same global step either way.  With a misaligned interval the re-jit shifts
  to the next K-boundary — still correct, but the stop_gradient changes the
  global-norm clip denominator, so the runs are no longer bit-comparable.
  The plan also refreshes at *checkpoint* boundaries (so a resume — which
  unavoidably applies the masks saved at the checkpoint step — re-derives
  exactly the uninterrupted run's state): the checkpoint cadence is thereby
  part of the numeric schedule, and runs are bit-comparable only when their
  checkpoint boundaries coincide too (``checkpoint_every`` aligned, or
  checkpointing off).
* Tier 2: when every monitored matrix is frozen, training terminates
  (Algorithm 1 line 24).  Detection needs no mid-block readback — the scan
  body itself no-ops every step past the all-frozen point, so the block the
  host is lagging behind on is a pure pass-through and the final state is
  bit-identical to a per-step run.
* Classic validation early stopping (the paper's FP+ES / LoRA+ES baselines)
  runs at the boundary that crosses each ``val_interval`` multiple (several
  multiples inside one block share the boundary's eval, each accruing
  patience) — its cost shows up as wall-clock, exactly the overhead Table 4
  reports.
* Fault tolerance: periodic async checkpoints land on block boundaries (so a
  resume lands on a boundary and the step-indexed data stream continues
  without replaying batches), auto-resume from the newest valid step, and a
  straggler watchdog.  The watchdog is block-granular: per-step times are
  derived from block *completion-event* timestamps (the lagged metric drain
  blocks until the device finishes the block, so consecutive completion
  deltas track device time whenever the device is the bottleneck; the clock
  restarts after boundary work so eval/checkpoint/recompile time never counts
  as block compute), the EMA is seeded only after the first block (compile
  time never pollutes it), and p50/p95 per-step times over a sliding window
  of blocks ride in the logged rows.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import os
import time
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, Iterator, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.config import ModelConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.core.partition import FreezePlan, freeze_plan
from repro.data.pipeline import Prefetcher, make_batches
from repro.distributed.sharding import active_mesh, active_rules
from repro.kernels.dispatch import resolve_backend
from repro.kernels.flash_attention import round_up
from repro.models.model import supports_segment_plan
from repro.optim.optimizer import (align_moments, align_packed_tree,
                                   expand_moments_host,
                                   expand_packed_tree_host)
from repro.robustness.faults import FaultyBatchSource, tag_grad_faults
from repro.robustness.harness import FaultActuator, GracefulShutdown
from repro.tracing import mark, span
from repro.train.state import (TrainState, init_train_state,
                               steps_completed)
from repro.train.step import make_eval_step, make_multi_step


@dataclass
class TrainResult:
    state: TrainState
    steps_run: int
    wall_time: float
    history: List[Dict[str, float]] = field(default_factory=list)
    stop_reason: str = "budget"
    recompiles: int = 0
    rollbacks: int = 0


def block_schedule(start_step: int, total_steps: int, k: int) -> List[int]:
    """Block sizes covering steps ``[start_step, total_steps)``: first align
    onto the K-grid (a resume from a foreign-interval checkpoint), then full
    K-blocks, then the tail — every boundary lands on ``min(m·K, total)``."""
    sizes: List[int] = []
    s = start_step
    if s % k and s < total_steps:
        sizes.append(min(k - s % k, total_steps - s))
        s += sizes[-1]
    while total_steps - s >= k:
        sizes.append(k)
        s += k
    if total_steps - s > 0:
        sizes.append(total_steps - s)
    return sizes


def _live_ranges(start: int, total: int,
                 skips: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Sub-ranges of ``[start, total)`` minus the rollback-skipped blocks."""
    out: List[Tuple[int, int]] = []
    cur = start
    for lo, hi in sorted(skips):
        if hi <= cur:
            continue
        if lo >= total:
            break
        if lo > cur:
            out.append((cur, lo))
        cur = max(cur, hi)
    if cur < total:
        out.append((cur, total))
    return out


def _plan_blocks(ranges: Sequence[Tuple[int, int]], k: int
                 ) -> List[Tuple[int, int]]:
    """(start, size) dispatch blocks: each live range scheduled on the K-grid."""
    out: List[Tuple[int, int]] = []
    for lo, hi in ranges:
        s = lo
        for sz in block_schedule(lo, hi, k):
            out.append((s, sz))
            s += sz
    return out


#: the most bytes of device-to-host copies in flight at once: on one TPU v5e
#: host an 8 GB state comes over in ~3 s in 2 GiB groups and in 6-11 s all
#: at once, while ``pinned_host`` destinations are slower still (PERF.md)
D2H_GROUP_BYTES = 2 << 30


@dataclass(frozen=True)
class HostSnapshot:
    """A state tree held in host memory (:func:`snapshot_to_host`)."""

    leaves: List[np.ndarray]    # in tree order
    treedef: Any
    targets: List[Any]          # each leaf's sharding, None if uncommitted


def _d2h_groups(leaves, limit: int):
    """Consecutive runs of ``leaves`` of at most ``limit`` bytes each (a
    larger leaf alone)."""
    group, n = [], 0
    for x in leaves:
        if group and n + x.nbytes > limit:
            yield group
            group, n = [], 0
        group.append(x)
        n += x.nbytes
    if group:
        yield group


def snapshot_to_host(tree) -> HostSnapshot:
    """Copy ``tree`` to host memory in the layout it has (row-packed moments,
    placeholders and error-feedback buffers as the compiled step holds them,
    no expansion), at most :data:`D2H_GROUP_BYTES` in flight at a time, and
    return once every copy has landed, so the tree's buffers may be donated
    afterwards.  The host holds each leaf once: a replicated leaf is read
    from one device, a sharded one shard by shard.  The copy is the span
    ``/repro/train/state_to_host`` with the snapshot's host ``bytes``."""
    leaves, treedef = jax.tree.flatten(tree)
    with span("/repro/train/state_to_host",
              bytes=sum(x.nbytes for x in leaves)):
        host = [h for group in _d2h_groups(leaves, D2H_GROUP_BYTES)
                for h in jax.device_get(group)]
    return HostSnapshot(host, treedef,
                        [x.sharding if x.committed else None for x in leaves])


def restore_snapshot(snap: HostSnapshot):
    """The tree :func:`snapshot_to_host` copied, back on the device: each leaf
    on the sharding it had, and one that was not committed to a device
    uncommitted again, so ``jit`` may still move it onto the step's mesh."""
    return jax.tree.unflatten(snap.treedef, [
        jax.device_put(h, t) for h, t in zip(snap.leaves, snap.targets)])


class _ChainedSource:
    """Chains per-range batch sources, tolerating exceptions from the active
    range: unlike a generator or ``itertools.chain``, a raise (an injected or
    real I/O error propagating up to the Prefetcher's bounded retry) does not
    kill the chain — the retry re-pulls the same range and the stream resumes.
    Factories are invoked lazily, one range at a time."""

    def __init__(self, factories: Sequence[Callable[[], Iterator]]):
        self._factories = list(factories)
        self._cur: Optional[Iterator] = None

    def __iter__(self) -> "_ChainedSource":
        return self

    def __next__(self):
        while True:
            if self._cur is None:
                if not self._factories:
                    raise StopIteration
                self._cur = iter(self._factories.pop(0)())
            try:
                return next(self._cur)
            except StopIteration:
                self._cur = None


@dataclass
class _Inflight:
    """One dispatched-but-undrained block."""

    start: int              # global step count before the block
    size: int
    metrics: Any            # device dict of (size,) metric arrays
    dispatched_at: float


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig, *,
                 repartition_interval: int = 25, log_every: int = 10,
                 log_path: Optional[str] = None,
                 progress_cb: Optional[Callable[[int, Optional[float]],
                                                None]] = None):
        self.cfg, self.tcfg = cfg, tcfg
        self.repartition_interval = repartition_interval
        self.log_every = log_every
        self.log_path = log_path
        # (last drained step, per-step EMA) observer — the elastic fleet's
        # heartbeat hook (elastic/heartbeat.py).  Must be cheap and non-raising
        # (called once per drained block on the training thread).
        self.progress_cb = progress_cb
        self.ckpt = (CheckpointManager(tcfg.checkpoint_dir,
                                       keep=tcfg.keep_checkpoints)
                     if tcfg.checkpoint_dir else None)

    # ------------------------------------------------------------------ init
    def init_state(self, seed: Optional[int] = None) -> TrainState:
        key = jax.random.PRNGKey(self.tcfg.seed if seed is None else seed)
        return init_train_state(key, self.cfg, self.tcfg)

    def _resume(self, state: TrainState) -> TrainState:
        if self.ckpt is None:
            return state
        # Self-healing restore: CRC-verify newest→oldest, quarantining corrupt
        # or partial steps, and land on the newest step that checks out.
        latest = self.ckpt.latest_valid()
        if latest is None:
            return state
        return self.ckpt.restore(latest, state)

    def _block_placer(self) -> Optional[Callable]:
        """Mesh-aware placer for stacked blocks (batch dim → data axis, same
        resolution as the launcher's batch shardings in ``launch/specs.py``)."""
        mesh = active_mesh()
        if mesh is None or mesh.devices.size <= 1:
            return None  # Prefetcher defaults to plain jax.device_put
        from repro.launch.specs import batch_block_shardings
        sh = batch_block_shardings(self.cfg, self.tcfg, mesh, active_rules())

        def place(block):
            return {k: jax.device_put(np.asarray(v), sh.get(k))
                    for k, v in block.items()}
        return place

    # ----------------------------------------------------------------- train
    def train(self, batches: Union[Iterator[Dict[str, np.ndarray]],
                                   Callable[[int], Iterator], None] = None,
              val_batches: Optional[List[Dict[str, np.ndarray]]] = None,
              state: Optional[TrainState] = None) -> TrainResult:
        cfg, tcfg = self.cfg, self.tcfg
        state = self._resume(state if state is not None else self.init_state())
        spec = build_monitor_spec(state.params, lora=tcfg.lora is not None)
        # Kernel backend is resolved once per run (static across Tier-1
        # re-jits); per-group fused-vs-jnp selection happens inside the step.
        backend = resolve_backend(tcfg.kernels)
        # Per-row moment packing changes moment shapes, which would break the
        # divisibility of the moment shardings derived from full param shapes
        # — keep it to single-device runs (the whole-type placeholder still
        # applies).  Gate on the *active mesh*, not the kernel backend: the
        # jnp backend carries no mesh even when one is in use.
        mesh = active_mesh()
        pack_rows = mesh is None or mesh.devices.size <= 1

        def derive(st) -> FreezePlan:
            """The freeze plan of ``st``'s frozen masks — a pure function of
            them, so a resume re-derives it bit-identically."""
            return freeze_plan(
                jax.device_get(st.grades.frozen), st.params, spec,
                cfg.n_layers, tcfg.segment_max,
                segments=tcfg.grades.enabled and supports_segment_plan(cfg),
                pack_rows=pack_rows)

        # Multiplicative LR backoff applied by the numerics guard: each
        # rollback halves (by rollback_lr_backoff) the LR of the re-dispatched
        # program.  Folded into the compiled step via a config replace, so the
        # schedule stays a pure function of opt.count.
        lr_scale = 1.0

        def repartition(st, new: FreezePlan, old_trainable=None):
            """``st`` with its moments and int8-EF buffers packed from
            ``old_trainable``'s layout (None: as a checkpoint stores them)
            to ``new``'s, and the step compiled for ``new``."""
            opt = align_moments(st.opt, st.params, tcfg, new.trainable,
                                old_trainable)
            ef = (None if st.ef_error is None else align_packed_tree(
                st.ef_error, st.params, jnp.float32, new.trainable,
                old_trainable))
            if opt is not st.opt or ef is not st.ef_error:
                st = dataclasses.replace(st, opt=opt, ef_error=ef)
            run_tcfg = (tcfg if lr_scale == 1.0 else
                        dataclasses.replace(tcfg, lr=tcfg.lr * lr_scale))
            return st, jax.jit(make_multi_step(cfg, run_tcfg, spec, new,
                                               backend=backend),
                               donate_argnums=0)

        # Checkpoints store moments in the plan-independent layout (full
        # buffers for any live rows, whole-type placeholders — see
        # _checkpoint_state), so a restored state packs down to whatever this
        # run's plan/segment_max implies, with no layout provenance needed.
        freeze = derive(state)
        state, step_fn = repartition(state, freeze)

        def _checkpoint_state(st):
            """Expand row-packed moments (and EF error buffers) to full
            buffers for the checkpoint: per-row packing is a function of this
            run's plan (segment_max), which a restart may change — on-disk
            layouts carry only the plan-independent cases (full /
            placeholder), and restore re-packs per the restoring run's own
            plan.  The expansion happens on the host (numpy scatter of the
            device_get'd packed rows), never re-materializing the full
            buffers in device memory."""
            with span("/repro/train/host_layout"):
                save_opt = expand_moments_host(st.opt, st.params, tcfg,
                                               freeze.trainable)
                if save_opt is not st.opt:
                    st = dataclasses.replace(st, opt=save_opt)
                if st.ef_error is not None:
                    save_ef = expand_packed_tree_host(st.ef_error, st.params,
                                                      freeze.trainable)
                    if save_ef is not st.ef_error:
                        st = dataclasses.replace(st, ef_error=save_ef)
            return st

        def guard_snapshot(st, step):
            """The numerics guard's rollback target: the state as the step
            holds it, copied to host memory."""
            with span("/repro/train/guard_snapshot", step=step):
                return snapshot_to_host(st)

        eval_fn = jax.jit(make_eval_step(cfg, tcfg)) if val_batches else None

        start_step = steps_completed(state)
        K = max(int(tcfg.sync_interval), 1)
        aligned_repart = round_up(max(self.repartition_interval, 1), K)
        val_interval = max(int(tcfg.val_interval_frac * tcfg.steps), 1)
        tier2_on = tcfg.grades.enabled and bool(spec.groups)
        placer = self._block_placer()
        fplan = tcfg.fault_plan
        act = FaultActuator(fplan)
        # SIGTERM becomes a drain request: finish the in-flight block, write a
        # boundary checkpoint synchronously, exit resumable (DESIGN.md §4).
        shutdown = GracefulShutdown()

        # Data: default stream is keyed by absolute step index (resume-safe);
        # a callable lets external datasets seek too; a bare iterator is used
        # as-is (the caller owns its resume offset).  Seekable sources can
        # also replay from a snapshot, which is what the numerics guard's
        # rollback needs — with a bare iterator a tripped guard aborts
        # instead of rolling back.
        can_replay = batches is None or callable(batches)
        guard_on = tcfg.numerics_guard and can_replay

        def build_source(ranges):
            if batches is not None and not callable(batches):
                it: Iterator = batches
                if fplan is not None and (fplan.has_grad_faults
                                          or fplan.has_comm_faults):
                    it = tag_grad_faults(it, fplan, start_step=start_step)
                if fplan is not None and fplan.has_io_faults:
                    it = FaultyBatchSource(it, fplan, start_step=start_step)
                return it

            def factory(lo, hi):
                def make():
                    if batches is None:
                        it = make_batches(cfg, tcfg, steps=hi - lo,
                                          start_step=lo)
                    else:
                        it = itertools.islice(batches(lo), hi - lo)
                    if fplan is not None and (fplan.has_grad_faults
                                              or fplan.has_comm_faults):
                        it = tag_grad_faults(it, fplan, start_step=lo)
                    # Outermost, so an injected OSError leaves no dead
                    # generator frame between the retrying consumer and the
                    # fault (robustness/faults.py).
                    if fplan is not None and fplan.has_io_faults:
                        it = FaultyBatchSource(it, fplan, start_step=lo)
                    return it
                return make
            return _ChainedSource([factory(lo, hi) for lo, hi in ranges])

        history: List[Dict[str, float]] = []
        last_row: Optional[Dict[str, float]] = None
        recompiles = 0
        stop = "budget"
        rollbacks = 0
        skips: List[Tuple[int, int]] = []
        # Boundary snapshot for the numerics guard: the whole state copied to
        # host memory in the step's own (packed) layout, refreshed at
        # each sync boundary once every drained block verified finite; the
        # plan it was packed to rides along.  Rollback = put it back and
        # re-derive the freeze plan from its masks — the same pure function
        # a restart runs, so replay is bit-deterministic.
        snapshot = guard_snapshot(state, start_step) if guard_on else None
        snapshot_step, snapshot_freeze = start_step, freeze
        best_val, val_bad = float("inf"), 0
        # --- watchdog state (block-granular; see module docstring) ---
        ema_dt: Optional[float] = None
        last_done: Optional[float] = None
        blocks_drained = 0
        compile_pending = False  # next drained block pays a (re)trace/compile
        dispatched_sizes: set = set()  # block shapes already traced/compiled
        dt_window: collections.deque = collections.deque(maxlen=64)
        tripped: Optional[Tuple[int, int]] = None  # offending (start, size)
        straggler_hit = False

        def drain(inflight: _Inflight) -> bool:
            with span("/repro/train/drain", step=inflight.start):
                return settle(inflight)

        def settle(inflight: _Inflight) -> bool:
            """Bulk device_get of one block's stacked metrics; returns True if
            Tier-2 (all monitored matrices frozen) was observed."""
            nonlocal ema_dt, last_done, blocks_drained, last_row, \
                compile_pending, tripped, straggler_hit
            act.before_drain(inflight.start, inflight.size)
            m = jax.device_get(inflight.metrics)
            t_done = time.perf_counter()
            block_dt = t_done - (last_done if last_done is not None
                                 else inflight.dispatched_at)
            last_done = t_done
            executed = np.asarray(m.get("executed",
                                        np.ones(inflight.size)), np.float64)
            n_exec = int(executed.sum())
            per_step = block_dt / max(n_exec, 1)
            # A block that was already finished when its predecessor drained
            # yields a near-zero completion delta (the host, not the device,
            # was the laggard — e.g. a long dispatch on a synchronous
            # backend).  Such artifacts would poison the EMA; detect them
            # against the dispatch→completion span and report that span as
            # the per-step estimate instead.
            dispatch_span = ((t_done - inflight.dispatched_at)
                             / max(n_exec, 1))
            artifact = per_step < 0.1 * dispatch_span
            if artifact:
                per_step = dispatch_span
            straggler = 0.0
            # Compile-polluted blocks (block 0, the first block after a Tier-1
            # re-jit, the first block of a new size — the tail or a
            # resume-alignment block retraces the scan) and host-lagged
            # artifacts are excluded from the EMA / p50-p95 window entirely.
            clean = blocks_drained >= 1 and not compile_pending and not artifact
            compile_pending = False
            if clean:
                if ema_dt is None:
                    ema_dt = per_step
                elif per_step > 3.0 * ema_dt and blocks_drained >= 2:
                    straggler = per_step / ema_dt
                ema_dt = 0.9 * ema_dt + 0.1 * per_step
                dt_window.append(per_step)
            blocks_drained += 1
            p50 = float(np.percentile(dt_window, 50)) if dt_window else per_step
            p95 = float(np.percentile(dt_window, 95)) if dt_window else per_step
            # Numerics guard: the all-finite sentinel rides the normal metric
            # drain, so detection lags dispatch by exactly one block — always
            # within the boundary snapshot's replay horizon.
            if tcfg.numerics_guard and "nonfinite" in m and \
                    float(np.max(np.asarray(m["nonfinite"], np.float64))) > 0:
                tripped = (inflight.start, inflight.size)
            # Watchdog escalation (satellite of DESIGN.md §4): a p95 that blew
            # past the healthy EMA by the configured factor means the device
            # (or a peer) is persistently slow — checkpoint and hand the
            # scheduling decision to the supervisor.
            if (tcfg.straggler_p95_abort > 0 and ema_dt is not None
                    and dt_window
                    and p95 > tcfg.straggler_p95_abort * ema_dt):
                straggler_hit = True
            tier2 = False
            for j in range(inflight.size):
                if executed[j] < 1.0:
                    continue  # post-termination no-op rows carry no step
                row = {k: float(v[j]) for k, v in m.items() if k != "executed"}
                row["step"] = inflight.start + j
                row["dt"] = per_step
                row["dt_p50"] = p50
                row["dt_p95"] = p95
                if straggler:
                    row["straggler"] = straggler
                last_row = row
                if row["step"] % self.log_every == 0 or row.get("all_frozen"):
                    history.append(row)
                    self._log(row)
            if tier2_on and float(np.max(np.asarray(m["all_frozen"],
                                                    np.float64))) >= 1.0:
                tier2 = True
            if "expert_assigned" in m:
                ran = executed >= 1.0
                load = {k: np.asarray(m[f"expert_{k}"], np.float64)[ran]
                        for k in ("assigned", "assigned_live", "busiest")}
                # the block's held-expert work, for the trace
                mark("/repro/train/expert_load", step=inflight.start,
                     assigned=int(load["assigned"].sum()),
                     assigned_live=int(load["assigned_live"].sum()),
                     busiest=int(load["busiest"].max(initial=0)))
            if self.progress_cb is not None:
                self.progress_cb(inflight.start + inflight.size, ema_dt)
            return tier2

        t0 = time.perf_counter()
        pending: Optional[_Inflight] = None
        s = start_step   # global steps covered by dispatched blocks
        try:
          # Attempt loop: one pass normally; a numerics-guard trip rolls back
          # to the boundary snapshot, skips the offending block, backs off the
          # LR, and replays (deterministically — the data stream is
          # step-keyed, so every surviving batch is bit-identical).
          while True:
            ranges = _live_ranges(snapshot_step, tcfg.steps, skips)
            blocks_plan = _plan_blocks(ranges, K)
            blocks = Prefetcher(build_source(ranges),
                                [sz for _, sz in blocks_plan],
                                depth=tcfg.prefetch_depth, place=placer,
                                retries=tcfg.prefetch_retries,
                                retry_backoff=tcfg.prefetch_retry_backoff,
                                stall_timeout=tcfg.prefetch_stall_timeout)
            pending = None
            tripped = None
            preempt = False
            best_val, val_bad = float("inf"), 0
            s = snapshot_step
            try:
              for bstart, size in blocks_plan:
                if shutdown.requested or straggler_hit:
                    # Graceful drain: stop dispatching; the pending block is
                    # settled below, then a boundary checkpoint is written.
                    preempt = True
                    break
                try:
                    with span("/repro/train/prefetch_wait", step=bstart):
                        block = next(blocks)
                except StopIteration:
                    break
                # An externally-supplied iterator can run dry mid-block; the
                # prefetcher then yields the short remainder — train it and
                # stop afterwards (the old per-step loop trained every batch).
                bsize = int(jax.tree.leaves(block)[0].shape[0])
                exhausted = bsize < size
                tier2 = False
                if bsize not in dispatched_sizes:
                    # New block shape => the dispatch below pays a fresh scan
                    # trace/compile.  Settle the pending block first so its
                    # completion delta stays clean, and mark the compiled
                    # block itself for exclusion from the timing stats.
                    if pending is not None:
                        tier2 = drain(pending)
                        pending = None
                        last_done = time.perf_counter()
                        if tripped is not None:
                            break
                        if tier2:
                            stop = "all_frozen"
                            break
                    dispatched_sizes.add(bsize)
                    compile_pending = True
                t_dispatch = time.perf_counter()
                with span("/repro/train/dispatch", step=bstart):
                    state, metrics = step_fn(state, block)
                cur = _Inflight(start=bstart, size=bsize, metrics=metrics,
                                dispatched_at=t_dispatch)
                prev_s, s = s, bstart + bsize
                # Planned kill/SIGTERM faults fire with this block in flight —
                # the worst-case moment for the recovery invariant.
                act.after_dispatch(bstart, s)
                # Drain the *previous* block while this one runs on device.
                tier2 = (pending is not None and drain(pending)) or tier2
                pending = cur
                if tripped is not None:
                    break
                need_t1 = (tcfg.grades.enabled and s % aligned_repart == 0
                           and s < tcfg.steps)
                val_crossings = (s // val_interval - prev_s // val_interval
                                 if tcfg.val_es and eval_fn is not None else 0)
                need_val = val_crossings > 0
                need_ckpt = (self.ckpt is not None and tcfg.checkpoint_every
                             and s // tcfg.checkpoint_every
                             > prev_s // tcfg.checkpoint_every)
                if tier2 or need_t1 or need_val or need_ckpt:
                    with span("/repro/train/boundary", step=bstart):
                        # Sync boundary: settle the just-dispatched block.
                        tier2 = drain(pending) or tier2
                        pending = None
                        if tripped is not None:
                            break
                        if tier2:
                            stop = "all_frozen"
                            break
                        # Refresh the freeze plan at repartition boundaries
                        # AND before a checkpoint: the saved moment layout
                        # must equal the pure function of the masks being
                        # saved, so a resume re-derives it exactly.
                        # Evaluating the (quantized) pure function more often
                        # cannot add recompiles — only distinct values count.
                        if (need_t1 or need_ckpt) and tcfg.grades.enabled:
                            with span("/repro/train/freeze_masks",
                                      step=bstart):
                                new = derive(state)
                            if new != freeze:
                                with span("/repro/train/repartition",
                                          step=bstart):
                                    state, step_fn = repartition(
                                        state, new, freeze.trainable)
                                    freeze = new
                                recompiles += 1
                                compile_pending = True  # paid at next dispatch
                        if need_val:
                            # One eval per boundary; a non-improving result
                            # accrues one patience count per val_interval
                            # multiple the block crossed (the K=1 plateau
                            # cadence), while an improving result counts as a
                            # single improvement — mid-block states were never
                            # materialized, so they cannot be evaluated
                            # separately.  Patience state (best_val/val_bad)
                            # is in-memory only: a resumed val-ES run restarts
                            # it.
                            with span("/repro/train/eval", step=bstart):
                                vl = float(np.mean([
                                    float(eval_fn(state.params,
                                                  state.base_params, vb))
                                    for vb in val_batches]))
                            if vl < best_val - tcfg.val_delta:
                                best_val, val_bad = vl, 0
                            else:
                                val_bad += val_crossings
                            if val_bad >= tcfg.val_patience:
                                stop = "val_es"
                                break
                        if need_ckpt:
                            with span("/repro/train/checkpoint", step=bstart):
                                self.ckpt.save(s, _checkpoint_state(state))
                                if fplan is not None and \
                                        fplan.corrupt_mode(s) is not None:
                                    # Planned corruption targets the *renamed*
                                    # step — wait for the async write, then
                                    # damage it.
                                    self.ckpt.wait()
                                    act.after_checkpoint(s,
                                                         tcfg.checkpoint_dir)
                        if guard_on:
                            # Everything drained above verified finite — this
                            # state is a safe rollback target.
                            snapshot = guard_snapshot(state, bstart)
                            snapshot_step, snapshot_freeze = s, freeze
                        # Boundary work (eval forward passes, the checkpoint's
                        # device_get, a Tier-1 recompile) is host/aux time,
                        # not block compute: restart the completion-delta
                        # clock so the next block's per-step estimate excludes
                        # it (no false straggler flags).
                        last_done = time.perf_counter()
                if exhausted:
                    break
              # settle the trailing block (skipped when a trip already broke
              # out: its successor consumed poisoned state and is discarded)
              if pending is not None and tripped is None:
                t2 = drain(pending)
                pending = None
                if t2 and tier2_on and tripped is None:
                    stop = "all_frozen"
            finally:
                blocks.close()

            # ---- adjudicate this attempt ----
            if tripped is not None:
                pending = None
                if not guard_on or rollbacks >= tcfg.max_rollbacks:
                    stop = "nonfinite_abort"
                    break
                rollbacks += 1
                lr_scale *= tcfg.rollback_lr_backoff
                skips.append((tripped[0], tripped[0] + tripped[1]))
                row = {"step": float(tripped[0]),
                       "rollback": float(rollbacks), "lr_scale": lr_scale}
                history.append(row)
                self._log(row)
                # Restore the boundary snapshot and re-derive the freeze plan
                # from its masks (identical to a cold restart from a
                # checkpoint of that boundary), then recompile with the
                # backed-off LR.  The snapshot is packed to the layout of its
                # boundary, which the re-derived one equals unless masks
                # froze since the last refresh (a val-only boundary).
                with span("/repro/train/rollback", step=tripped[0]):
                    state = restore_snapshot(snapshot)
                    freeze = derive(state)
                    state, step_fn = repartition(state, freeze,
                                                 snapshot_freeze.trainable)
                recompiles += 1
                dispatched_sizes = set()
                compile_pending = False
                last_done = None
                continue
            if stop == "budget" and (preempt or shutdown.requested
                                     or straggler_hit):
                # Graceful drain (SIGTERM) or straggler escalation: all
                # dispatched work is settled and finite — write a synchronous
                # boundary checkpoint and exit with a resumable stop reason.
                if self.ckpt is not None:
                    with span("/repro/train/checkpoint", step=s):
                        self.ckpt.save(s, _checkpoint_state(state),
                                       blocking=True)
                stop = ("straggler_abort"
                        if straggler_hit and not shutdown.requested
                        else "preempted")
            break
        finally:
            shutdown.uninstall()

        # Always record the terminal step (budget end mid-log-interval, or a
        # val-ES/Tier-2 break whose last step missed the log cadence).
        if last_row is not None and (not history
                                     or history[-1]["step"] != last_row["step"]):
            history.append(last_row)
            self._log(last_row)

        if self.ckpt is not None:
            self.ckpt.wait()
        wall = time.perf_counter() - t0
        return TrainResult(state=state,
                           steps_run=steps_completed(state) - start_step,
                           wall_time=wall, history=history, stop_reason=stop,
                           recompiles=recompiles, rollbacks=rollbacks)

    def _log(self, metrics: Dict[str, float]):
        if self.log_path:
            os.makedirs(os.path.dirname(self.log_path) or ".", exist_ok=True)
            with open(self.log_path, "a") as f:
                f.write(json.dumps(metrics) + "\n")
