"""train_step / eval_step / multi_step factories.

``make_train_step(cfg, tcfg, spec, freeze=...)`` closes over everything static
— the :class:`~repro.core.partition.FreezePlan` the host derived at the last
repartition boundary among it — and returns a pure ``(state, batch) -> (state,
metrics)`` suitable for ``jax.jit`` (the launcher adds in/out shardings and
donates the state).

One step = microbatched grads (lax.scan accumulation) → optional int8-EF
compression → GradES monitor update (Algorithm 1) → masked optimizer update.

``make_multi_step`` is the sync-boundary variant (DESIGN.md §4): it
``lax.scan``s the single step over a stacked ``(K, ...)`` batch block so the
host only wakes once per K steps — per-step metrics come back stacked as
``(K,)`` arrays in one bulk transfer, and Tier-2 is handled *inside* the scan
(once every monitored matrix is frozen, remaining steps are ``lax.cond``
no-ops), so a block dispatched past the all-frozen point leaves the state
bit-identical to a per-step run that stopped exactly there.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, TrainConfig
from repro.core.grades import (MonitorSpec, all_frozen, frozen_fraction,
                               get_path, grades_update, set_path)
from repro.core.lora import merge_lora
from repro.core.partition import FreezePlan, freeze_plan, static_freeze_tree
from repro.distributed import (active_mesh, active_rules,
                               compress_with_feedback, explicit_reduce_axes,
                               n_compressible, param_partition_specs,
                               reduce_gradients, suspend_mesh)
from repro.distributed.sharding import (ShardingRules, mesh_axis_size,
                                        model_axis_size)
from repro.kernels.dispatch import KernelBackend, resolve_backend
from repro.models import model
from repro.optim.optimizer import (apply_updates, global_norm, lr_at,
                                   packed_row_counts)
from repro.tracing import mark


#: per-step metrics that count work over the whole batch: summed, not
#: averaged, over microbatches and data-parallel shards
SUMMED_METRICS = ("expert_load",)


def _combine(metrics, mean, total):
    return {k: (total(v) if k in SUMMED_METRICS else mean(v))
            for k, v in metrics.items()}


def expert_load_metrics(load, spec: MonitorSpec, frozen) -> Dict[str, Any]:
    """One step's held-expert counts from the model's ``expert_load`` (L, E):
    tokens x picks routed to held experts, those routed to live rows (a
    ``(layer, expert)`` row is live unless every routed-expert matrix froze
    it), and the busiest row's."""
    rows = [frozen[n] for n, (_, gran) in spec.groups.items()
            if gran == 2 and frozen[n].shape == load.shape]
    dead = jnp.all(jnp.stack(rows), axis=0) if rows else False
    load = load.astype(jnp.float32)
    return {"expert_assigned": load.sum(),
            "expert_assigned_live": jnp.where(dead, 0.0, load).sum(),
            "expert_busiest": load.max()}


def _loss(params, base_params, batch, cfg: ModelConfig, tcfg: TrainConfig,
          attn_args=None, plan=None):
    if tcfg.lora is not None:
        merged = merge_lora(base_params, params, tcfg.lora)
        return model.loss_fn(merged, batch, cfg, remat=tcfg.remat,
                             attn_args=attn_args, plan=plan)
    return model.loss_fn(params, batch, cfg, remat=tcfg.remat,
                         attn_args=attn_args, plan=plan)


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, spec: MonitorSpec,
                    freeze: Optional[FreezePlan] = None,
                    backend: Optional[KernelBackend] = None,
                    param_specs=None):
    """``backend`` (resolved from ``tcfg.kernels`` when None) selects the fused
    Pallas monitor+update pipeline or the jnp reference path, per stacked group
    (DESIGN.md §3).  It is static per compiled step — the Tier-1 re-jit in the
    loop reuses the same backend.

    Under a multi-device mesh (picked up from the ``use_mesh`` context at
    factory time) the fused kernels are shard_map'd over each leaf's
    PartitionSpec.  ``param_specs`` (path -> spec) may be passed explicitly;
    when None it is derived once, at first trace, from the model's
    logical-axis tree against the backend's mesh — the same resolution the
    launcher uses for state shardings.  LoRA parameter trees carry no
    logical-axis table, so sharded LoRA runs keep the jnp path per leaf.

    ``freeze`` (a :class:`~repro.core.partition.FreezePlan`, None when
    nothing is frozen) is static per compiled step and refreshed by the
    trainer's re-jit (DESIGN.md §2): its whole-type set wraps those leaves in
    ``stop_gradient``, its segment plan chains the layer scan so per-layer
    frozen rows stop costing dW FLOPs, its ``trainable`` packs their
    optimizer moments to live rows only, and its reduce plan drives the
    freeze-aware explicit data-parallel reduce (DESIGN.md §3): on an eligible
    pure-DP mesh (``distributed/reduce.py::explicit_reduce_axes``) gradients
    are computed inside a shard_map manual over the DP axes and psum'd
    per-leaf, with frozen leaves/rows dropped from the collective — their
    gradients are exactly zero, so the drop is bit-identical to the full-tree
    reduce while the bytes leave the compiled HLO.
    """
    backend = resolve_backend(tcfg.kernels) if backend is None else backend
    dp_mesh = active_mesh()
    dp_axes = explicit_reduce_axes(dp_mesh, tcfg)
    mesh = backend.mesh
    rules = active_rules() if mesh is not None else None
    # attention rides the same resolved backend as the GradES kernels, so
    # --kernels controls the whole hot path; a non-empty cfg.attn_backend
    # overrides inside models.common.attn_call_args (DESIGN.md §3b).
    attn_args = {"backend": backend}
    if dp_axes is not None and backend.sharded:
        # Pure data parallel: the loss runs per shard in the manual body
        # below, where a shard_map-wrapped kernel cannot nest, so attention
        # takes the kernels unwrapped.  The reduced grads, params and moments
        # are replicated, so the GradES kernels run whole on every device:
        # shard_map over replicated specs (rules that map no axis).
        attn_args = {"backend": dataclasses.replace(backend, mesh=None)}
        rules = ShardingRules()
    _derived: Dict[str, Any] = {}

    def freeze_for(params) -> FreezePlan:
        """``freeze``, or the plan of nothing frozen (derived once)."""
        if freeze is not None:
            return freeze
        if "freeze" not in _derived:
            _derived["freeze"] = freeze_plan({}, params, spec, cfg.n_layers,
                                             tcfg.segment_max, segments=False,
                                             pack_rows=False)
        return _derived["freeze"]

    def specs_for(params):
        if param_specs is not None:
            return param_specs
        if mesh is None or not backend.use_pallas or tcfg.lora is not None:
            return None
        if "specs" not in _derived:
            axes = model.param_logical_axes(cfg, model_axis_size(mesh))
            _derived["specs"] = param_partition_specs(params, axes, mesh, rules)
        return _derived["specs"]

    def grads_of(params, base_params, batch):
        fz = freeze_for(params)

        def f(p):
            p = static_freeze_tree(p, spec, fz.whole)
            return _loss(p, base_params, batch, cfg, tcfg, attn_args,
                         fz.segments)
        (loss, metrics), grads = jax.value_and_grad(f, has_aux=True)(params)
        return loss, metrics, grads

    def local_grads(params, base_params, batch, microbatch):
        """Grads over (this shard of) the batch, microbatch-accumulated when
        ``microbatch`` splits it."""
        if microbatch and microbatch < batch["tokens"].shape[0]:
            B = batch["tokens"].shape[0]
            mb, n = microbatch, B // microbatch
            split = jax.tree.map(
                lambda x: x.reshape((n, mb) + x.shape[1:]), batch)

            def acc(carry, b):
                loss, metrics, grads = grads_of(params, base_params, b)
                g_acc, l_acc = carry
                return ((jax.tree.map(jnp.add, g_acc, grads), l_acc + loss),
                        metrics)

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
            (grads, loss), metrics = jax.lax.scan(acc, (zero, 0.0), split)
            grads = jax.tree.map(lambda g: g / n, grads)
            return loss / n, _combine(metrics, lambda m: m.mean(0),
                                      lambda m: m.sum(0)), grads
        return grads_of(params, base_params, batch)

    if dp_axes is not None:
        # Freeze-aware explicit DP reduce (DESIGN.md §3): grads are computed
        # on each shard's local batch rows inside a shard_map manual over the
        # DP axes — params/base_params replicated, batch split on dim 0 —
        # then reduced per-leaf under the boundary ReducePlan.  pmean of
        # shard-means == global-batch mean (equal shards); the logical
        # sharding context is suspended inside the body because every mesh
        # axis is already manual there.
        ndev = mesh_axis_size(dp_mesh, dp_axes)
        mb_local = (tcfg.microbatch // ndev
                    if tcfg.microbatch and tcfg.microbatch % ndev == 0 else 0)

        def _reduce_body(params, base_params, batch):
            with suspend_mesh():
                loss, metrics, grads = local_grads(params, base_params,
                                                   batch, mb_local)
            grads = reduce_gradients(grads, dp_axes,
                                     freeze_for(params).reduce)
            loss = jax.lax.pmean(loss, dp_axes)
            metrics = _combine(metrics, lambda m: jax.lax.pmean(m, dp_axes),
                               lambda m: jax.lax.psum(m, dp_axes))
            return loss, metrics, grads

        _sharded = jax.shard_map(_reduce_body, mesh=dp_mesh,
                                 in_specs=(P(), P(), P(dp_axes)),
                                 out_specs=(P(), P(), P()), check_vma=False)

        def dispatch_grads(params, base_params, batch):
            bp = base_params if base_params is not None else ()
            return _sharded(params, bp, batch)
    else:
        def dispatch_grads(params, base_params, batch):
            return local_grads(params, base_params, batch, tcfg.microbatch)

    # Deterministic non-finite injection (robustness/faults.py): the batch
    # stream carries a per-step ``fault_gain`` scalar (1.0 on healthy steps,
    # NaN/Inf at planned ones) that multiplies ONE monitored group's gradient
    # in-jit.  ×1.0 is a bitwise no-op, so a tagged-but-healthy step matches
    # the untagged program numerically; with no plan the multiply isn't traced
    # at all.
    fp = tcfg.fault_plan
    fault_target = None
    if fp is not None and fp.has_grad_faults and spec.groups:
        names = sorted(spec.groups)
        fault_target = names[fp.grad_target_index(len(names))]

    def splice_fault(grads, gain):
        for p in spec.groups[fault_target][0]:
            grads = set_path(grads, p, get_path(grads, p) * gain)
        return grads

    def train_step(state, batch):
        batch = dict(batch)
        fault_gain = batch.pop("fault_gain", None)
        comm_gain = batch.pop("comm_gain", None)
        params = state.params
        with jax.named_scope("fwd_bwd"):
            loss, metrics, grads = dispatch_grads(params, state.base_params,
                                                  batch)

        if fault_target is not None and fault_gain is not None:
            grads = splice_fault(grads, fault_gain)

        trainable = freeze_for(params).trainable
        ef_error = state.ef_error
        if tcfg.grad_compression == "int8_ef" and ef_error is not None:
            fault_index = None
            if fp is not None and comm_gain is not None:
                fault_index = fp.comm_target_index(
                    n_compressible(grads, trainable))
            with jax.named_scope("ef_compress"):
                grads, ef_error = compress_with_feedback(
                    grads, ef_error, trainable=trainable,
                    fault_gain=comm_gain if fault_index is not None else None,
                    fault_index=fault_index)

        pspecs = specs_for(params)
        with jax.named_scope("grades_monitor"):
            grades, frozen = grades_update(state.grades, grads, spec,
                                           tcfg.grades, tcfg.steps,
                                           backend=backend,
                                           param_specs=pspecs)
        with jax.named_scope("optimizer"):
            new_params, new_opt = apply_updates(
                params, grads, state.opt, tcfg, trainable=trainable,
                spec=spec, group_frozen=frozen, backend=backend,
                param_specs=pspecs)
        metrics = dict(metrics)
        load = metrics.pop("expert_load", None)
        if load is not None:
            metrics.update(expert_load_metrics(load, spec,
                                               state.grades.frozen))
        metrics["grad_norm"] = global_norm(grads)
        metrics["frozen_frac"] = frozen_fraction(frozen)
        metrics["all_frozen"] = all_frozen(frozen)
        metrics["lr"] = jnp.asarray(lr_at(new_opt.count, tcfg), jnp.float32)
        if tcfg.numerics_guard:
            # All-finite sentinel (DESIGN.md §4): loss covers the forward,
            # global_norm covers every gradient leaf (one non-finite element
            # poisons the whole sum-of-squares), and both scalars are already
            # computed — so the sentinel is two isfinite ops piggybacked on
            # the existing per-block metrics, no extra device sync.  The host
            # checks it at the normal block drain and rolls back.
            finite = jnp.isfinite(loss) & jnp.isfinite(metrics["grad_norm"])
            metrics["nonfinite"] = 1.0 - finite.astype(jnp.float32)
        new_state = type(state)(step=state.step + 1, params=new_params,
                                base_params=state.base_params, opt=new_opt,
                                grades=grades, ef_error=ef_error)
        return new_state, metrics

    return train_step


def make_multi_step(cfg: ModelConfig, tcfg: TrainConfig, spec: MonitorSpec,
                    freeze: Optional[FreezePlan] = None,
                    backend: Optional[KernelBackend] = None,
                    param_specs=None):
    """Sync-boundary step: ``(state, block) -> (state, metrics)`` where
    ``block`` is a stacked ``(K, B, ...)`` batch pytree and every metric comes
    back as a ``(K,)`` array (one bulk ``device_get`` per block, DESIGN.md §4).

    The scan body wraps the single step in a Tier-2 gate: when all monitored
    matrices are already frozen at the start of a step, the step is a
    ``lax.cond`` no-op (state — including ``state.step`` and ``opt.count`` —
    passes through unchanged; the metrics row reports ``executed=0``,
    ``all_frozen=1``).  The host therefore never needs a mid-block readback to
    stop at exactly the right step: blocks dispatched past termination are
    pure pass-throughs and the final state is bit-identical to
    ``sync_interval=1``.  The same factory serves K=1, so both paths run the
    identical scan-body HLO.

    Each trace marks ``/repro/train/packed_rows`` once with the step's
    Tier-1.5 layout (``optimizer.packed_row_counts``): the row-packed
    leaves, their live rows, and the in-place row writes they compile to.
    """
    single = make_train_step(cfg, tcfg, spec, freeze, backend=backend,
                             param_specs=param_specs)
    tier2 = tcfg.grades.enabled and bool(spec.groups)

    def multi_step(state, block):
        mark("/repro/train/packed_rows", **packed_row_counts(
            freeze.trainable if freeze is not None else None))

        def run(state, batch):
            new_state, m = single(state, batch)
            return new_state, dict(m, executed=jnp.float32(1))

        def body(state, batch):
            if not tier2:
                return run(state, batch)

            def skip(s):
                m_sds = jax.eval_shape(single, s, batch)[1]
                m = {k: jnp.zeros(v.shape, v.dtype) for k, v in m_sds.items()}
                m["frozen_frac"] = jnp.ones_like(m["frozen_frac"])
                m["all_frozen"] = jnp.ones_like(m["all_frozen"])
                return s, dict(m, executed=jnp.float32(0))

            return jax.lax.cond(all_frozen(state.grades.frozen),
                                skip, lambda s: run(s, batch), state)

        return jax.lax.scan(body, state, block)

    return multi_step


def make_eval_step(cfg: ModelConfig, tcfg: TrainConfig):
    attn_args = {"backend": resolve_backend(tcfg.kernels)}

    def eval_step(params, base_params, batch):
        loss, metrics = _loss(params, base_params, batch, cfg, tcfg, attn_args)
        return metrics["ce"]
    return eval_step
