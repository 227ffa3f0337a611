"""Optimizers (AdamW / SGD-momentum) with GradES-aware masked updates.

Three masking tiers compose here (DESIGN.md §2):

* ``freeze_masks`` (dynamic, per step): boolean pytree from GradES; a frozen
  matrix's parameters and moments are left bit-identical — exactly the paper's
  "skip update (but gradient still flows)" (Algorithm 1, line 15).
* ``trainable`` (static, per repartition): params statically frozen by Tier-1 hold a
  1-element moment placeholder instead of full m/v buffers, freeing 8 bytes/param
  of optimizer state for converged matrix types.
* **Per-row placeholders (Tier 1.5)**: a ``trainable`` leaf may be a host-side
  boolean *row mask* (granularity shape, True = live), in which case m/v store
  only the live rows — ``(n_live,) + trailing`` — so frozen (layer, expert)
  rows free their 8 bytes/param *before* the whole type converges.  The update
  gathers live rows with static indices (compile-time slices), runs the fused
  or jnp update on the packed arrays, and writes params back in place, one
  static ``dynamic_update_slice`` per run of live rows; frozen rows stay
  bit-identical.  ``align_moments`` re-packs m/v at sync boundaries and
  after checkpoint restore (packing is a pure function of the boundary masks,
  so a resumed run re-derives the identical layout).

Moments can be stored in bf16 (``opt_state_dtype``) for trillion-parameter configs.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import TrainConfig


@dataclass
class OptState:
    count: jax.Array
    m: Any
    v: Any


jax.tree_util.register_dataclass(OptState, data_fields=["count", "m", "v"],
                                 meta_fields=[])


def _placeholder(dtype):
    return jnp.zeros((1,), dtype)


def _is_row_mask(t) -> bool:
    return isinstance(t, np.ndarray)


def _live_rows(t: "np.ndarray") -> "np.ndarray":
    """Static indices of the live rows in the collapsed granularity axis."""
    return np.nonzero(np.asarray(t, bool).reshape(-1))[0]


def _live_runs(t: "np.ndarray") -> list:
    """Static ``(start, stop)`` runs of consecutive live rows in the collapsed
    granularity axis, in order (Python ints)."""
    idx = _live_rows(t)
    if idx.size == 0:
        return []
    cuts = np.flatnonzero(np.diff(idx) != 1) + 1
    return [(int(r[0]), int(r[-1]) + 1) for r in np.split(idx, cuts)]


def packed_row_counts(trainable) -> Dict[str, int]:
    """How much of ``trainable`` takes the Tier-1.5 packed-row path: the
    row-packed leaves, their live rows, and the in-place row writes
    (one per run of live rows) the update makes."""
    masks = [t for t in jax.tree.leaves(trainable) if _is_row_mask(t)]
    return {"leaves": len(masks),
            "rows": sum(int(np.asarray(t, bool).sum()) for t in masks),
            "runs": sum(len(_live_runs(t)) for t in masks)}


def moment_shape(p, t):
    """Expected m/v shape for a param under a ``trainable`` leaf value."""
    if _is_row_mask(t):
        n_live = int(np.asarray(t, bool).sum())
        return ((n_live,) + tuple(p.shape[t.ndim:])) if n_live else (1,)
    return tuple(p.shape) if t else (1,)


def _moment_zeros(p, t, dt):
    shape = moment_shape(p, t)
    return _placeholder(dt) if shape == (1,) else jnp.zeros(shape, dt)


def init_opt_state(params, tcfg: TrainConfig, trainable=None) -> OptState:
    dt = jnp.dtype(tcfg.opt_state_dtype)
    if trainable is None:
        trainable = jax.tree.map(lambda _: True, params)
    zeros = jax.tree.map(lambda p, t: _moment_zeros(p, t, dt),
                         params, trainable)
    if tcfg.optimizer == "sgd":
        return OptState(count=jnp.zeros((), jnp.int32), m=zeros,
                        v=jax.tree.map(lambda _: _placeholder(dt), params))
    return OptState(count=jnp.zeros((), jnp.int32), m=zeros,
                    v=jax.tree.map(lambda z: jnp.zeros_like(z), zeros))


def lr_at(step, tcfg: TrainConfig):
    warm = max(int(tcfg.warmup_frac * tcfg.steps), 1)
    frac = jnp.minimum(step / warm, 1.0)
    if tcfg.schedule == "constant":
        return tcfg.lr * frac
    prog = jnp.clip((step - warm) / max(tcfg.steps - warm, 1), 0.0, 1.0)
    return tcfg.lr * frac * 0.5 * (1 + jnp.cos(jnp.pi * prog))


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def apply_updates(params, grads, opt: OptState, tcfg: TrainConfig, *,
                  freeze_masks=None, trainable=None,
                  lr: Optional[jax.Array] = None,
                  spec=None, group_frozen=None, backend=None,
                  param_specs=None):
    """Returns (new_params, new_opt).  ``freeze_masks``: True = GradES-frozen.

    Fused path (DESIGN.md §3): when ``spec`` (a MonitorSpec), ``group_frozen``
    (the per-group freeze flags from ``grades_update``) and a Pallas ``backend``
    are given, every stacked monitored leaf goes through the frozen-gated
    ``masked_adamw``/``masked_sgd`` kernel — frozen layers cost one SMEM flag
    load instead of streaming p/m/v/g — with dynamic ``lr``/``count`` operands
    (no recompile under a schedule).  Non-stacked / ragged / unmonitored leaves
    fall back to the jnp ``where``-masked update below, per leaf, in the same
    call.

    ``param_specs`` (path -> PartitionSpec) drives the shard_map wrapping of
    the kernels under a sharded backend; leaves without a usable spec take the
    jnp path (one-time warning when pallas was forced).

    A ``trainable`` leaf that is a boolean row mask (Tier 1.5) routes through
    the packed-row path: live rows are gathered with *static* indices, the
    packed m/v (``(n_live,) + trailing``) are updated — through the same fused
    kernel when eligible — and only the live rows of ``p`` are written back,
    so frozen rows stay bit-identical without streaming their moments.
    """
    from repro.core.grades import _key_path, broadcast_mask
    from repro.kernels import dispatch as _dispatch

    count = opt.count + 1
    lr = lr_at(count, tcfg) if lr is None else lr
    if tcfg.grad_clip:
        gn = global_norm(grads)
        scale = jnp.minimum(1.0, tcfg.grad_clip / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    if trainable is None:
        trainable = jax.tree.map(lambda _: True, params)
    use_pallas = (backend is not None and backend.use_pallas
                  and spec is not None and group_frozen is not None
                  and tcfg.optimizer in ("adamw", "sgd"))
    if freeze_masks is None and (spec is None or group_frozen is None):
        # No per-group flags to build masks from lazily below: default to an
        # all-live mask tree.
        freeze_masks = jax.tree.map(lambda _: jnp.zeros((), bool), params)

    def upd(p, g, m, v, mask, train):
        if not train:
            return p, m, v
        g32 = g.astype(jnp.float32)
        live = ~mask  # True where the matrix still trains
        if tcfg.optimizer == "sgd":
            m32 = m.astype(jnp.float32)
            m_new = jnp.where(live, tcfg.b1 * m32 + g32, m32)
            step_vec = lr * m_new
            v_new = v
        else:
            m32, v32 = m.astype(jnp.float32), v.astype(jnp.float32)
            m_new = jnp.where(live, tcfg.b1 * m32 + (1 - tcfg.b1) * g32, m32)
            v_new = jnp.where(live, tcfg.b2 * v32 + (1 - tcfg.b2) * g32 * g32, v32)
            mhat = m_new / (1 - tcfg.b1 ** count)
            vhat = v_new / (1 - tcfg.b2 ** count)
            step_vec = lr * mhat / (jnp.sqrt(vhat) + tcfg.eps)
        p32 = p.astype(jnp.float32)
        decay = lr * tcfg.weight_decay * p32 if tcfg.weight_decay else 0.0
        p_new = jnp.where(live, p32 - step_vec - decay, p32)
        dt = jnp.dtype(tcfg.opt_state_dtype)
        return (p_new.astype(p.dtype), m_new.astype(dt),
                v_new.astype(dt) if v.size > 1 else v)

    flat_kp, treedef = jax.tree_util.tree_flatten_with_path(params)
    paths = [_key_path(kp) for kp, _ in flat_kp]
    flat_p = [leaf for _, leaf in flat_kp]
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt.m)
    flat_v = treedef.flatten_up_to(opt.v)
    flat_mask = (treedef.flatten_up_to(freeze_masks)
                 if freeze_masks is not None else [None] * len(flat_p))
    flat_train = treedef.flatten_up_to(trainable)
    p2g = spec.path_to_group if spec is not None else {}
    param_specs = param_specs or {}
    new_p, new_m, new_v = [], [], []
    for path, p, g, m, v, mask, train in zip(paths, flat_p, flat_g, flat_m,
                                             flat_v, flat_mask, flat_train):
        group = p2g.get(path) if group_frozen is not None else None
        flags = group_frozen[group] if group is not None else None
        if _is_row_mask(train):
            pn, mn, vn = _packed_row_update(
                p, g, m, v, train, flags, lr, count, tcfg, use_pallas,
                backend, upd)
            new_p.append(pn)
            new_m.append(mn)
            new_v.append(vn)
            continue
        if (use_pallas and train and flags is not None
                and _dispatch.fused_ok(p, flags.shape, backend,
                                       param_specs.get(path))
                and _dispatch.moments_fusable(m, v, p, tcfg.optimizer)):
            pn, mn, vn = _dispatch.fused_masked_update(
                p, g, m, v, flags, lr, count, tcfg, backend,
                param_specs.get(path))
        else:
            if mask is None:
                mask = (broadcast_mask(flags, p) if flags is not None
                        else jnp.zeros((), bool))
            pn, mn, vn = upd(p, g, m, v, mask, train)
        new_p.append(pn)
        new_m.append(mn)
        new_v.append(vn)
    unflat = jax.tree_util.tree_unflatten
    return (unflat(treedef, new_p),
            OptState(count=count, m=unflat(treedef, new_m),
                     v=unflat(treedef, new_v)))


def _packed_row_update(p, g, m, v, row_mask, flags, lr, count,
                       tcfg: TrainConfig, use_pallas: bool, backend, upd):
    """Tier-1.5 update for one leaf whose moments hold live rows only.

    ``row_mask`` is the host boolean live-row mask (granularity shape); its
    nonzero indices are compile-time constants, so the gathers lower to
    static slices, and the updated rows go back with one
    ``dynamic_update_slice`` at a constant start per run of live rows, which
    XLA applies in place to the (donated) stacked buffer.  A scatter there
    lowers, on the TPU, to a loop over the rows that copies the whole stack
    in and out of its carry on every trip.  ``flags`` (the group's *dynamic*
    freeze array) still masks rows that froze since the last boundary.
    """
    from repro.core.grades import broadcast_mask
    from repro.kernels import dispatch as _dispatch

    live_idx = _live_rows(row_mask)
    if live_idx.size == 0:
        return p, m, v
    gran = row_mask.ndim
    trailing = p.shape[gran:]
    pc = p.reshape((-1,) + trailing)
    p_live = pc[live_idx]
    g_live = g.reshape((-1,) + trailing)[live_idx]
    fl_live = (flags.reshape(-1)[live_idx] if flags is not None
               else jnp.zeros((live_idx.size,), bool))
    # A row-masked trainable leaf MUST come paired with align_moments-packed
    # buffers — a silent no-update here would de-facto freeze the leaf, so
    # fail at trace time instead.
    if m.shape != p_live.shape or (tcfg.optimizer != "sgd"
                                   and v.shape != p_live.shape):
        raise ValueError(
            f"per-row trainable mask expects moments packed to "
            f"{p_live.shape}, got m{tuple(m.shape)}/v{tuple(v.shape)} — "
            f"run align_moments before building the step")
    if (use_pallas and not backend.sharded
            and _dispatch.fused_eligible(p_live, fl_live.shape)):
        pn_live, mn, vn = _dispatch.fused_masked_update(
            p_live, g_live, m, v, fl_live, lr, count, tcfg, backend, None)
    else:
        pn_live, mn, vn = upd(p_live, g_live, m, v,
                              broadcast_mask(fl_live, p_live), True)
    off = 0
    for start, stop in _live_runs(row_mask):
        pc = jax.lax.dynamic_update_slice_in_dim(
            pc, pn_live[off:off + stop - start], start, axis=0)
        off += stop - start
    return pc.reshape(p.shape), mn, vn


def align_packed_tree(tree, params, dtype, trainable, old_trainable=None):
    """Re-pack any params-shaped auxiliary buffer tree (optimizer moments,
    error-feedback buffers) to the layout ``trainable`` implies — full /
    1-element placeholder / live-rows-packed per leaf, same transitions as
    :func:`align_moments`.  Returns ``tree`` itself when nothing changes."""
    flat_kp, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_p = [leaf for _, leaf in flat_kp]
    flat_x = treedef.flatten_up_to(tree)
    flat_t = treedef.flatten_up_to(trainable)
    flat_t_old = (treedef.flatten_up_to(old_trainable)
                  if old_trainable is not None else [None] * len(flat_p))
    dt = jnp.dtype(dtype)
    changed = False
    new_x = []
    for p, x, t, t_old in zip(flat_p, flat_x, flat_t, flat_t_old):
        ex = _align_leaf(p, x, t, t_old, dt)
        changed |= ex is not x
        new_x.append(ex)
    if not changed:
        return tree
    return jax.tree_util.tree_unflatten(treedef, new_x)


def align_moments(opt: OptState, params, tcfg: TrainConfig, trainable,
                  old_trainable=None) -> OptState:
    """Re-pack per-row moment buffers to match ``trainable`` (Tier 1.5).

    Called at sync boundaries when new rows froze (``old_trainable`` is the
    previous layout; monotone freezing guarantees new live ⊆ old live) and
    after checkpoint restore (``old_trainable=None``: the stored layout is
    recognized by shape — packed checkpoints restore across plan changes
    because packing is a pure function of the restored masks, and legacy
    full-buffer or whole-type-placeholder checkpoints are packed/kept as
    needed).  Returns ``opt`` itself when nothing changes.
    """
    dt = jnp.dtype(tcfg.opt_state_dtype)
    new_m = align_packed_tree(opt.m, params, dt, trainable, old_trainable)
    new_v = (opt.v if tcfg.optimizer == "sgd"
             else align_packed_tree(opt.v, params, dt, trainable,
                                    old_trainable))
    if new_m is opt.m and new_v is opt.v:
        return opt
    return OptState(count=opt.count, m=new_m, v=new_v)


def expand_packed_tree_host(tree, params, trainable):
    """Host-side (numpy) expansion of a row-packed buffer tree to full shape,
    for checkpointing: packed rows are ``device_get`` and scattered into host
    zeros, so the full-size buffers never materialize in device memory (that
    would transiently re-spend the exact HBM the packing freed).  Full
    buffers and placeholders pass through untouched; the result mixes device
    and numpy leaves and is only suitable for saving.  Returns ``tree``
    itself when nothing changes."""
    flat_kp, treedef = jax.tree_util.tree_flatten_with_path(params)
    flat_p = [leaf for _, leaf in flat_kp]
    flat_x = treedef.flatten_up_to(tree)
    flat_t = treedef.flatten_up_to(trainable)
    changed = False

    def one(p, cur, t):
        nonlocal changed
        if not _is_row_mask(t) or tuple(cur.shape) != moment_shape(p, t) \
                or cur.size == 1:
            return cur  # full / placeholder / sgd-v stub
        host = np.asarray(jax.device_get(cur))
        full = np.zeros((int(np.prod(p.shape[:t.ndim])),) + host.shape[1:],
                        host.dtype)
        full[_live_rows(t)] = host
        changed = True
        return full.reshape(p.shape)

    new_x = [one(p, x, t) for p, x, t in zip(flat_p, flat_x, flat_t)]
    if not changed:
        return tree
    return jax.tree_util.tree_unflatten(treedef, new_x)


def expand_moments_host(opt: OptState, params, tcfg: TrainConfig,
                        trainable) -> OptState:
    """Checkpoint-layout expansion of the optimizer moments (see
    :func:`expand_packed_tree_host`)."""
    new_m = expand_packed_tree_host(opt.m, params, trainable)
    new_v = (opt.v if tcfg.optimizer == "sgd"
             else expand_packed_tree_host(opt.v, params, trainable))
    if new_m is opt.m and new_v is opt.v:
        return opt
    return OptState(count=opt.count, m=new_m, v=new_v)


def _align_leaf(p, cur, t, t_old, dt):
    target = moment_shape(p, t)
    if tuple(cur.shape) == target:
        return cur
    if target == (1,):
        return _placeholder(cur.dtype if cur.size > 1 else dt)
    if tuple(cur.shape) == tuple(p.shape):
        # full buffer (live run at its first per-row freeze, or a legacy /
        # expanded checkpoint): gather the target live rows
        gran = t.ndim if _is_row_mask(t) else 0
        return cur.reshape((-1,) + tuple(p.shape[gran:]))[_live_rows(t)]
    if t_old is not None and _is_row_mask(t_old) \
            and tuple(cur.shape) == moment_shape(p, t_old):
        old_idx = _live_rows(t_old)
        if not _is_row_mask(t):
            # packed checkpoint restored where packing is off (e.g. onto a
            # multi-device mesh): expand back to a full buffer — the packed-
            # out rows are frozen, so their (dead) moments re-init as zeros
            trailing = tuple(p.shape[t_old.ndim:])
            full = jnp.zeros((int(np.prod(p.shape[:t_old.ndim])),) + trailing,
                             cur.dtype)
            return full.at[old_idx].set(cur).reshape(p.shape)
        new_idx = _live_rows(t)
        pos = np.searchsorted(old_idx, new_idx)
        if (pos >= old_idx.size).any() or \
                not np.array_equal(old_idx[pos], new_idx):
            raise ValueError(
                "non-monotone moment repack: new live rows are not a subset "
                "of the previous layout")
        return cur[pos]
    raise ValueError(
        f"cannot align moment buffer of shape {tuple(cur.shape)} to target "
        f"{target} for a param of shape {tuple(p.shape)} — unknown packing "
        f"provenance (checkpoint saved under incompatible freeze masks?)")
