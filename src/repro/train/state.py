"""TrainState: everything that must survive a checkpoint/restart, as one pytree.

GradES state is part of it by construction — freeze decisions survive node failures
and elastic restarts (DESIGN.md §4).

``state.step`` counts *executed* optimizer steps and is authoritative for
resume: under the sync-boundary trainer the host dispatches whole blocks, but
Tier-2-gated no-op steps inside a block do not advance it, and checkpoints are
written at block boundaries, so a restored ``step`` always lands on a boundary
and the step-indexed data stream (``data/pipeline.py``) continues exactly
where the failed run stopped."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import GradESConfig, ModelConfig, TrainConfig
from repro.core.grades import (GradESState, MonitorSpec, build_monitor_spec,
                               init_grades_state)
from repro.core.lora import init_lora_params
from repro.core.partition import trainable_mask
from repro.optim.optimizer import OptState, init_opt_state


@dataclass
class TrainState:
    step: jax.Array
    params: Any              # trainable tree (LoRA adapters when lora is on)
    base_params: Any         # LoRA: the frozen base tree; else None
    opt: OptState
    grades: GradESState
    ef_error: Any            # int8 grad-compression error-feedback buffer (or None)


jax.tree_util.register_dataclass(
    TrainState,
    data_fields=["step", "params", "base_params", "opt", "grades", "ef_error"],
    meta_fields=[])


def init_train_state(key, cfg: ModelConfig, tcfg: TrainConfig) -> TrainState:
    from repro.models import model
    k1, k2 = jax.random.split(key)
    base = model.init_params(k1, cfg)
    if tcfg.lora is not None:
        params = init_lora_params(k2, base, tcfg.lora)
        base_params = base
        spec = build_monitor_spec(params, lora=True)
    else:
        params = base
        base_params = None
        spec = build_monitor_spec(params)
    trainable = trainable_mask(params, spec, frozenset())
    opt = init_opt_state(params, tcfg, trainable)
    grades = init_grades_state(params, spec, tcfg.grades)
    ef = None
    if tcfg.grad_compression == "int8_ef":
        ef = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                      base_params=base_params, opt=opt, grades=grades, ef_error=ef)


def monitor_spec_for(state: TrainState, tcfg: TrainConfig) -> MonitorSpec:
    return build_monitor_spec(state.params, lora=tcfg.lora is not None)


def steps_completed(state: TrainState) -> int:
    """Host-side executed-step count (one tiny scalar pull).  The controller
    reads this once at resume and once at the end of a run — never per step."""
    return int(jax.device_get(state.step))
