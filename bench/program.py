"""The system under test, driven through its normal entry.

Everything the benchmark takes from the program is here: the ``Trainer``
(host controller, prefetcher, sync blocks, repartition boundaries, numerics
guard) driving the compiled multi-step with the GradES kernels and flash
attention, and the program's ``TrainState`` built around the benchmark's
weights.

One ``Trainer`` object runs the whole cell in two calls of ``train``:

1. the first sync block (steps ``0..K``) from a plain iterator of ``K``
   batches.  Its state is what ``correct`` reads: the per-step losses, each
   leaf's parameter change, AdamW's second moment and the GradES monitor
   norms.
2. from step ``K`` on, from the step-keyed batch source, with the numerics
   guard's boundary snapshots on, as users run it.  The measured window opens
   at the drain of the first repartition boundary after this call's first
   (compiled) block and closes at a later boundary: the longest whole number
   of repartition periods that fits in ``seconds``, at least two.  The clock
   is the trainer's ``progress_cb``.  At the close the benchmark sends itself
   SIGTERM, which the trainer takes as a graceful drain: it finishes the
   boundary and returns, compiling nothing.
"""
from __future__ import annotations

import itertools
import signal
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.grades import build_monitor_spec, init_grades_state
from repro.core.partition import (fully_frozen_types, plan_row_masks,
                                  segment_plan, trainable_mask)
from repro.optim.optimizer import init_opt_state
from repro.train.loop import Trainer
from repro.train.state import TrainState

from cell import Cell, model_config, train_config

#: JAX's spans for building a program (jax/_src/dispatch.py): tracing to a
#: jaxpr, lowering to MLIR, and the backend compile, which also wraps a load
#: from the persistent compilation cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
#: the fewest whole repartition periods a window holds
MIN_PERIODS = 2


def group_name(matrix: str) -> str:
    return f"layers/{matrix}"


def build_state(cell: Cell, params, tcfg) -> TrainState:
    """The program's state around the benchmark's weights, with the cell's
    frozen rows written into ``grades.frozen`` and AdamW's moments laid out
    (packed to live rows) as the trainer derives them from those masks."""
    cfg = model_config(cell)
    spec = build_monitor_spec(params)
    frozen = {name: np.zeros(spec.mask_shape(params, name), bool)
              for name in spec.groups}
    for matrix, rows in cell.frozen_rows().items():
        frozen[group_name(matrix)] = np.asarray(rows, bool)
    static = fully_frozen_types(frozen)
    plan = segment_plan(frozen, spec, cfg.n_layers, tcfg.segment_max)
    trainable = trainable_mask(params, spec, static,
                               plan_row_masks(plan, spec, frozen))

    @jax.jit
    def zeros(p):
        opt = init_opt_state(p, tcfg, trainable)
        grades = init_grades_state(p, spec, tcfg.grades)
        grades.frozen = {k: jnp.asarray(v) for k, v in frozen.items()}
        return opt, grades

    opt, grades = zeros(params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      base_params=None, opt=opt, grades=grades, ef_error=None)


class Window:
    """The trainer's ``progress_cb``: opens the measured window at the drain
    of boundary ``first_boundary`` and closes it at the first later boundary,
    :data:`MIN_PERIODS` periods on or more, after which one more period would
    not fit in ``seconds`` (so it spans the longest whole number of periods
    that fits, at least :data:`MIN_PERIODS`).  The tracer, if any, starts at
    the drain of step ``first_boundary - K``."""

    def __init__(self, period: int, first_boundary: int, seconds: float,
                 trace_at: int, tracer=None):
        self.period, self.first_boundary = period, first_boundary
        self.seconds, self.trace_at, self.tracer = seconds, trace_at, tracer
        self.t0 = self.t1 = self.wall0 = self.wall1 = None
        self.s0 = self.s1 = None

    @property
    def closed(self) -> bool:
        return self.t1 is not None

    def __call__(self, step: int, _ema) -> None:
        now = time.perf_counter()
        if self.closed:
            return
        if step == self.trace_at and self.tracer is not None:
            self.tracer.start()
        if step % self.period:
            return
        if self.t0 is None:
            if step >= self.first_boundary:
                self.t0, self.wall0, self.s0 = now, time.time(), step
                if self.tracer is not None:
                    self.tracer.open()
            return
        periods = (step - self.s0) // self.period
        elapsed = now - self.t0
        if periods >= MIN_PERIODS and elapsed + elapsed / periods > \
                self.seconds:
            self.t1, self.wall1, self.s1 = now, time.time(), step
            if self.tracer is not None:
                self.tracer.close()
            signal.raise_signal(signal.SIGTERM)   # the trainer's drain path


class CompileCounter:
    """Programs traced, lowered or compiled (or loaded from the persistent
    cache), seen through JAX's monitoring spans: a re-jit inside the window
    shows whether or not the persistent cache holds it."""

    def __init__(self):
        self.spans: List[tuple] = []
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event: str, start: float, end: float, **_kw) -> None:
        if event in COMPILE_EVENTS:
            self.spans.append((start, end))

    def between(self, wall0: float, wall1: float) -> int:
        return sum(1 for s, e in self.spans if e >= wall0 and s <= wall1)

    def close(self) -> None:
        jax.monitoring.unregister_event_time_span_listener(self._on_span)


def first_block_readings(state: TrainState, p0, cell: Cell) -> Dict[str, Any]:
    """What ``correct`` reads from the state after the first block, per
    parameter leaf (paths joined by ``/``) and per monitored layer row."""
    frozen_rows = {group_name(m): np.asarray(r, bool)
                   for m, r in cell.frozen_rows().items()}

    @jax.jit
    def read(params, p0, v, last_norm):
        change = jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32) - b.astype(jnp.float32)))), params, p0)
        vnorm = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x.astype(jnp.float32))),
                             v)
        moved = {}
        for name, rows in frozen_rows.items():
            leaf = name.split("/")[1]
            diff = jnp.abs(params["layers"][leaf] - p0["layers"][leaf])
            moved[name] = jnp.max(jnp.where(
                jnp.asarray(rows).reshape((-1,) + (1,) * (diff.ndim - 1)),
                diff, 0.0))
        return change, vnorm, moved, last_norm

    change, vnorm, moved, last_norm = jax.device_get(read(
        state.params, p0, state.opt.v, state.grades.last_norm))
    flat = lambda t: {jax.tree_util.keystr(k, simple=True, separator="/"):
                      float(v) for k, v in
                      jax.tree_util.tree_leaves_with_path(t)}
    return {"change": flat(change), "grad": flat(vnorm),
            "frozen_moved": {k: float(v) for k, v in moved.items()},
            "monitor": {k: np.asarray(v, np.float64).tolist()
                        for k, v in last_norm.items()}}


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def run(cell: Cell, seed: int, seconds: float, batches, params_fn,
        *, tracer=None, first_block_only: bool = False) -> Dict[str, Any]:
    """Drive the program through one cell run (see the module docstring).

    ``batches(step)`` yields the step-keyed batches; ``params_fn()`` makes the
    weights.  ``tracer`` (optional) has ``start()``, ``open()``, ``close()``
    and ``stop()``.  Returns the losses and readings of the first block, and
    unless ``first_block_only`` the window's steps and clock, its losses,
    the compiles inside it and the device memory peak."""
    tcfg = train_config(cell, seed)
    K, period = tcfg.sync_interval, cell.steps_per_period
    # the first boundary after the second call's first (compiled) block
    first_boundary = (2 * K // period + 1) * period
    window = Window(period, first_boundary, seconds,
                    trace_at=first_boundary - K, tracer=tracer)
    trainer = Trainer(model_config(cell), tcfg,
                      repartition_interval=int(
                          cell.traffic["trainer"]["repartition_interval"]),
                      log_every=1, progress_cb=window)
    first = trainer.train(
        batches=itertools.islice(batches(0), K),
        state=build_state(cell, params_fn(), tcfg))
    losses = [r["loss"] for r in first.history]
    state = first.state
    del first
    p0 = params_fn()
    readings = first_block_readings(state, p0, cell)
    del p0
    out: Dict[str, Any] = {"losses": losses, "readings": readings}
    if first_block_only:
        return out
    counter = CompileCounter()
    try:
        res = trainer.train(batches=batches, state=state)
    finally:
        counter.close()
        if tracer is not None and window.t0 is not None:
            tracer.stop()
    del state
    if not window.closed:
        raise RuntimeError(f"the window never closed: training stopped at "
                           f"{res.steps_run} steps ({res.stop_reason})")
    out.update(
        window=window,
        window_losses=[r["loss"] for r in res.history
                       if window.s0 <= r["step"] < window.s1],
        compiles_in_window=counter.between(window.wall0, window.wall1),
        memory_peak_bytes=peak_bytes(jax.local_devices()[:cell.chips]))
    return out
