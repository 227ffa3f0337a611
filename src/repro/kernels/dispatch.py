"""Kernel dispatch layer: route the GradES hot path — and the attention hot
path (§3b) — to Pallas or jnp, on any mesh (DESIGN.md §3).

The train step's per-parameter work — the Eq.-1 monitor norm and the masked
optimizer update — has two interchangeable implementations:

* the fused Pallas kernels (:mod:`repro.kernels.grades_norm`,
  :mod:`repro.kernels.masked_adamw`), which hit the roofline minimum of HBM
  passes and skip frozen layers entirely, and
* the pure-jnp reference path (:func:`repro.optim.optimizer.apply_updates`'s
  ``where``-masked update), which works for any leaf shape.

``resolve_backend(tcfg.kernels)`` picks once per (re)jit: ``"pallas"`` forces
the kernels (interpret mode when not on TPU, so CPU tests exercise the same
code path), ``"jnp"`` forces the reference, and ``"auto"`` uses the kernels on
TPU — including sharded multi-device meshes — and jnp elsewhere
(interpret-mode Pallas is an emulation, not a win, for production CPU runs).

Per-*group* selection then happens leaf by leaf: a monitored parameter is
``fused_eligible`` when it is a stacked ``(gran..., trailing...)`` tensor whose
leading axes match the group's freeze-flag shape — everything else (ragged,
non-stacked, unmonitored) falls back to jnp within the same step.

Sharded dispatch
----------------
``pallas_call`` has no GSPMD partitioning rule, so under a multi-device mesh
every fused call is wrapped in :func:`jax.shard_map`
over the leaf's :class:`~jax.sharding.PartitionSpec` (derived from the model's
logical-axis tree — ``distributed.sharding.param_partition_specs``):

* the elementwise ``masked_adamw``/``masked_sgd`` kernels run unchanged on
  each shard; the tiny ``(L,)``/``(L, E)`` freeze flags ride in replicated and
  are sliced inside the shard when a granularity axis itself lands on a mesh
  axis;
* ``grades_norm`` computes a *partial* per-layer L1 delta-norm over its local
  trailing-dim shard and the wrapper ``psum``s the partials over exactly the
  mesh axes that shard trailing dims, keeping Eq. 1 consistent with the
  single-device path.

Layouts the shard mapper cannot handle (no spec recorded for the leaf, a mesh
axis reused across dims, a granularity extent that does not divide its mesh
axes) fall back to jnp per leaf; when ``kernels="pallas"`` was *forced*, a
one-time warning names the first such layout instead of silently compiling
the kernel with replication.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.distributed.sharding import (ATTN_KV_AXES, ATTN_MASK_AXES,
                                        ATTN_Q_AXES, active_mesh,
                                        active_rules, logical_to_spec,
                                        mesh_axis_size)
from repro.kernels import ops
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_decode_ref)
from repro.kernels.flash_attention import flash_attention

#: logical axes of the paged KV pool (N, page_size, KV, hd) — the pool has no
#: batch dim (slots of one data shard share it), so only kv_heads can shard.
PAGED_POOL_AXES = (None, None, "kv_heads", None)
#: per-slot page table (B, P) / valid counts (B,) follow the batch axis.
PAGED_TABLE_AXES = ("batch", None)

BACKEND_CHOICES = ("pallas", "jnp", "auto")


@dataclass(frozen=True)
class KernelBackend:
    """Resolved backend: static per compiled step (a re-jit picks it up)."""

    kind: str         # "pallas" | "jnp"
    interpret: bool   # Pallas interpret mode (True anywhere but real TPU)
    #: multi-device mesh the kernel calls shard_map over (None = single device)
    mesh: Optional[Mesh] = None
    #: True when the user forced "pallas" (drives the fallback warning)
    forced: bool = False

    @property
    def use_pallas(self) -> bool:
        return self.kind == "pallas"

    @property
    def sharded(self) -> bool:
        return self.mesh is not None


def resolve_backend(choice: str = "auto", platform: str | None = None,
                    mesh: Optional[Mesh] = None) -> KernelBackend:
    """``mesh`` defaults to the active ``use_mesh`` context; single-device
    meshes are treated as no mesh (the kernels need no wrapping there)."""
    if choice not in BACKEND_CHOICES:
        raise ValueError(f"kernels must be one of {BACKEND_CHOICES}, got {choice!r}")
    platform = platform or jax.default_backend()
    on_tpu = platform == "tpu"
    mesh = active_mesh() if mesh is None else mesh
    if mesh is not None and mesh.devices.size <= 1:
        mesh = None
    if choice == "jnp":
        return KernelBackend("jnp", False)
    if choice == "pallas":
        return KernelBackend("pallas", interpret=not on_tpu, mesh=mesh,
                             forced=True)
    return (KernelBackend("pallas", False, mesh) if on_tpu
            else KernelBackend("jnp", False))


def fused_eligible(leaf, flags_shape) -> bool:
    """A leaf can take the fused kernels iff its leading axes are the freeze
    granularity axes (stacked layout) and there is a trailing extent to tile."""
    gran = len(flags_shape)
    return (leaf.ndim > gran and tuple(leaf.shape[:gran]) == tuple(flags_shape)
            and leaf.size > 0)


# ---------------------------------------------------------------------------
# Sharded-layout vetting
# ---------------------------------------------------------------------------

def _pad_spec(pspec: Optional[P], ndim: int) -> Tuple:
    """A PartitionSpec padded with None to one entry per array dim."""
    parts = tuple(pspec) if pspec is not None else ()
    return parts + (None,) * (ndim - len(parts))


def _part_axes(part) -> Tuple[str, ...]:
    if part is None:
        return ()
    return (part,) if isinstance(part, str) else tuple(part)


def shard_restriction(leaf, gran: int, pspec: Optional[P],
                      mesh: Mesh) -> Optional[str]:
    """Why the shard mapper cannot take this (leaf, spec) — None when it can.

    The derivation path (``param_partition_specs`` -> ``logical_to_spec``)
    only emits dividing specs, so in practice this rejects leaves with *no*
    recorded spec (e.g. LoRA trees) and hand-built specs that reuse a mesh
    axis or leave a granularity row ragged across its shards.
    """
    if pspec is None:
        return "no PartitionSpec recorded for leaf"
    if len(tuple(pspec)) > leaf.ndim:
        return (f"PartitionSpec has {len(tuple(pspec))} entries for a "
                f"{leaf.ndim}-d leaf")
    parts = _pad_spec(pspec, leaf.ndim)
    seen = set()
    for part in parts:
        for a in _part_axes(part):
            if a in seen:
                return f"mesh axis {a!r} reused across dims"
            if a not in mesh.axis_names:
                return f"unknown mesh axis {a!r}"
            seen.add(a)
    for d, part in enumerate(parts):
        n = mesh_axis_size(mesh, _part_axes(part) or None)
        if leaf.shape[d] % n != 0:
            kind = "granularity" if d < gran else "trailing"
            return (f"{kind} dim {d} ({leaf.shape[d]}) not divisible by its "
                    f"mesh axes ({n})")
    return None


_warned_fallbacks: set = set()


def _warn_forced_fallback(backend: KernelBackend, reason: str) -> None:
    if backend.forced and reason not in _warned_fallbacks:
        _warned_fallbacks.add(reason)
        warnings.warn(
            f"kernels='pallas' forced, but a leaf's layout cannot be "
            f"shard-mapped ({reason}); falling back to the jnp path for such "
            f"leaves instead of compiling the kernel with replication.",
            RuntimeWarning, stacklevel=3)


def fused_ok(leaf, flags_shape, backend: KernelBackend,
             pspec: Optional[P]) -> bool:
    """The single dispatch predicate: stacked layout + (under a mesh) a layout
    the shard mapper handles.  Warns once per reason when pallas was forced."""
    if not fused_eligible(leaf, flags_shape):
        return False
    if not backend.sharded:
        return True
    reason = shard_restriction(leaf, len(flags_shape), pspec, backend.mesh)
    if reason is not None:
        _warn_forced_fallback(backend, reason)
        return False
    return True


# ---------------------------------------------------------------------------
# Fused calls (single-device bodies + shard_map wrappers)
# ---------------------------------------------------------------------------

def _collapse_gran(x, gran: int):
    """(g0, g1, ..., rest...) -> (g0*g1*..., rest...) for the kernels' leading-L
    layout; gran-2 expert tensors become one freeze row per (layer, expert)."""
    lead = math.prod(x.shape[:gran])
    return x.reshape((lead,) + x.shape[gran:])


def _slice_flags(flags, gran_parts, mesh: Mesh):
    """Restrict replicated freeze flags to this shard's granularity rows.

    For each granularity dim that lands on mesh axes, the local row range is
    ``[idx * local, (idx+1) * local)`` where ``idx`` linearizes the device's
    coordinates along those axes in the same row-major order GSPMD uses for a
    tuple entry of a PartitionSpec.
    """
    for d, part in enumerate(gran_parts):
        axes = _part_axes(part)
        if not axes:
            continue
        idx = jnp.int32(0)
        size = 1
        for a in axes:
            idx = idx * mesh_axis_size(mesh, a) + jax.lax.axis_index(a)
            size *= mesh_axis_size(mesh, a)
        local = flags.shape[d] // size
        flags = jax.lax.dynamic_slice_in_dim(flags, idx * local, local, axis=d)
    return flags


def _local_grades_norm(g, prev, gran: int, backend: KernelBackend,
                       flags=None):
    """Single-shard Eq.-1 body: (partial norm shaped ``g.shape[:gran]``,
    new_prev shaped like ``g``) in one kernel pass; ``flags`` (freeze state,
    shape ``g.shape[:gran]``) gates frozen rows to a flag load."""
    gran_shape = g.shape[:gran]
    norm, new_prev = ops.grades_norm(_collapse_gran(g, gran),
                                     _collapse_gran(prev, gran),
                                     None if flags is None
                                     else flags.reshape(-1),
                                     interpret=backend.interpret)
    return norm.reshape(gran_shape), new_prev.reshape(g.shape)


def fused_grades_norm(g, prev, gran: int, backend: KernelBackend,
                      pspec: Optional[P] = None, flags=None):
    """Fused Eq.-1 monitor: returns (unnormalized L1 delta-norm with shape
    ``g.shape[:gran]``, new_prev shaped like ``g``).

    ``flags`` is the group's freeze array (shape = the ``gran`` leading axes
    of ``g``): frozen rows skip the delta pass entirely — zero norm, ``prev``
    kept — matching the gated jnp path in ``core/grades.py``.

    Under a sharded backend the kernel runs per shard via shard_map: each
    shard reduces its local trailing elements, then partials are ``psum``'d
    over exactly the mesh axes that shard trailing dims, so the result equals
    the single-device norm (up to float reduction order).  Flags enter
    replicated and are sliced to the shard's granularity rows, as in
    :func:`fused_masked_update`.
    """
    if not backend.sharded:
        return _local_grades_norm(g, prev, gran, backend, flags)
    mesh = backend.mesh
    parts = _pad_spec(pspec, g.ndim)
    trailing_axes = tuple(a for part in parts[gran:] for a in _part_axes(part))
    if flags is None:
        flags = jnp.zeros(g.shape[:gran], bool)

    def local(g_l, prev_l, flags_full):
        fl = _slice_flags(flags_full, parts[:gran], mesh)
        norm, new_prev = _local_grades_norm(g_l, prev_l, gran, backend, fl)
        if trailing_axes:
            norm = jax.lax.psum(norm, trailing_axes)
        return norm, new_prev

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(P(*parts), P(*parts), P()),
                         out_specs=(P(*parts[:gran]), P(*parts)),
                         check_vma=False)(g, prev, flags)


def _local_masked_update(p, g, m, v, flags, lr, count, tcfg,
                         backend: KernelBackend):
    """Single-shard frozen-gated optimizer update for one stacked leaf."""
    gran = flags.ndim
    shape = p.shape
    c = lambda x: _collapse_gran(x, gran)
    if tcfg.optimizer == "sgd":
        p3, m3 = ops.masked_sgd(
            c(p), c(g), c(m), flags.reshape(-1), lr,
            b1=tcfg.b1, weight_decay=tcfg.weight_decay,
            interpret=backend.interpret)
        return p3.reshape(shape), m3.reshape(shape), v
    p3, m3, v3 = ops.masked_adamw(
        c(p), c(g), c(m), c(v), flags.reshape(-1), lr, count,
        b1=tcfg.b1, b2=tcfg.b2, eps=tcfg.eps, weight_decay=tcfg.weight_decay,
        interpret=backend.interpret)
    return p3.reshape(shape), m3.reshape(shape), v3.reshape(shape)


def fused_masked_update(p, g, m, v, flags, lr, count, tcfg,
                        backend: KernelBackend, pspec: Optional[P] = None):
    """Fused frozen-gated optimizer update for one stacked leaf.

    ``flags`` is the group's boolean freeze array (shape = leading ``gran``
    axes of ``p``); ``lr``/``count`` are *dynamic* operands — no recompile
    under a schedule.  Returns (p', m', v') with frozen rows bit-identical.

    Under a sharded backend the update is elementwise per shard, so the
    kernel runs unchanged inside shard_map; the flags enter replicated and
    are sliced to the shard's granularity rows when a granularity axis lands
    on a mesh axis.  ``lr``/``count`` stay replicated scalars.
    """
    if not backend.sharded:
        return _local_masked_update(p, g, m, v, flags, lr, count, tcfg, backend)
    mesh = backend.mesh
    gran = flags.ndim
    parts = _pad_spec(pspec, p.ndim)
    tsp, rep = P(*parts), P()
    lr = jnp.asarray(lr, jnp.float32)
    count = jnp.asarray(count, jnp.float32)

    if tcfg.optimizer == "sgd":
        # SGD carries its (placeholder) v through untouched — keep it out of
        # the mapped body so its 1-element shape never meets the leaf spec.
        def local_sgd(p_l, g_l, m_l, flags_full, lr_l):
            fl = _slice_flags(flags_full, parts[:gran], mesh)
            p3, m3, _ = _local_masked_update(p_l, g_l, m_l, None, fl, lr_l,
                                             None, tcfg, backend)
            return p3, m3

        p3, m3 = jax.shard_map(local_sgd, mesh=mesh,
                               in_specs=(tsp, tsp, tsp, rep, rep),
                               out_specs=(tsp, tsp),
                               check_vma=False)(p, g, m, flags, lr)
        return p3, m3, v

    def local(p_l, g_l, m_l, v_l, flags_full, lr_l, count_l):
        fl = _slice_flags(flags_full, parts[:gran], mesh)
        return _local_masked_update(p_l, g_l, m_l, v_l, fl, lr_l, count_l,
                                    tcfg, backend)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(tsp, tsp, tsp, tsp, rep, rep, rep),
                         out_specs=(tsp, tsp, tsp),
                         check_vma=False)(p, g, m, v, flags, lr, count)


# ---------------------------------------------------------------------------
# Attention dispatch (DESIGN.md §3b)
# ---------------------------------------------------------------------------

#: trailing-dim ceiling for one (bq, hd)/(bk, hd) tile pair + scratch to sit
#: comfortably in VMEM with double buffering at the default 256-row blocks.
MAX_FLASH_HEAD_DIM = 512


def normalize_backend(backend) -> KernelBackend:
    """Accept a resolved :class:`KernelBackend`, a choice string, or None
    (= ``"auto"``) — attention call sites pass whatever the config gave them."""
    if isinstance(backend, KernelBackend):
        return backend
    return resolve_backend(backend or "auto")


def flash_attention_restriction(q_shape, k_shape, dtype) -> Optional[str]:
    """Why the flash kernel cannot take this attention call — None when it
    can.  Per-call and shape-static, so routing never recompiles the step."""
    if len(q_shape) != 5 or len(k_shape) != 4:
        return (f"unexpected layout q{tuple(q_shape)} / k{tuple(k_shape)} "
                f"(want (B,S,KV,G,hd) / (B,T,KV,hd))")
    hd = q_shape[-1]
    if q_shape[1] <= 1:
        return "decode-shaped query (S=1): the dense path is cheaper"
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return f"non-float dtype {jnp.dtype(dtype).name}"
    if hd > MAX_FLASH_HEAD_DIM:
        return (f"head_dim {hd} exceeds the kernel VMEM tile budget "
                f"({MAX_FLASH_HEAD_DIM})")
    if hd % 8 != 0:
        return f"head_dim {hd} not a multiple of the 8-sublane layout"
    return None


def _warn_forced_attention_fallback(backend: KernelBackend,
                                    reason: str) -> None:
    if backend.forced and reason not in _warned_fallbacks:
        _warned_fallbacks.add(reason)
        warnings.warn(
            f"kernels='pallas' forced, but this attention call cannot take "
            f"the flash kernel ({reason}); falling back to the jnp "
            f"full/blockwise path for such calls.",
            RuntimeWarning, stacklevel=3)


def flash_ok(q, k, backend: KernelBackend) -> bool:
    """Dispatch predicate for one attention call; warns once per reason when
    pallas was forced but the call falls back to the blockwise jnp path."""
    if not backend.use_pallas:
        return False
    reason = flash_attention_restriction(q.shape, k.shape, q.dtype)
    if reason is not None:
        _warn_forced_attention_fallback(backend, reason)
        return False
    return True


def fused_flash_attention(q, k, v, *, causal: bool, window: int = 0,
                          kv_valid=None, backend: KernelBackend,
                          block_q: int = 256, block_k: int = 256):
    """The flash fwd+bwd pair, shard_map-wrapped under a multi-device mesh.

    Attention is independent per (batch row, KV head), so the kernel runs
    unchanged on each shard of the ``(batch -> data, kv_heads -> model)``
    activation layout (``ATTN_*_AXES``); axes that don't divide are dropped by
    the same ``logical_to_spec`` resolution the launcher uses, degrading to
    replicated compute rather than wrong results.  Sequence-sharded layouts
    (``seq_parallel_attn``) never reach this path — the model layer keeps the
    jnp formulation there, since a shard would need its neighbours' KV.
    """
    kw = dict(causal=causal, window=window, block_q=block_q, block_k=block_k,
              interpret=backend.interpret)
    if not backend.sharded:
        return flash_attention(q, k, v, kv_valid=kv_valid, **kw)
    mesh = backend.mesh
    rules = active_rules()
    qspec = logical_to_spec(ATTN_Q_AXES, shape=q.shape, mesh=mesh, rules=rules)
    kvspec = logical_to_spec(ATTN_KV_AXES, shape=k.shape, mesh=mesh,
                             rules=rules)
    if kv_valid is None:  # keep the no-mask fast path (no dead-row pass)
        def local(q_l, k_l, v_l):
            return flash_attention(q_l, k_l, v_l, **kw)

        return jax.shard_map(local, mesh=mesh,
                             in_specs=(qspec, kvspec, kvspec),
                             out_specs=qspec, check_vma=False)(q, k, v)
    mspec = logical_to_spec(ATTN_MASK_AXES, shape=kv_valid.shape, mesh=mesh,
                            rules=rules)

    def local(q_l, k_l, v_l, m_l):
        return flash_attention(q_l, k_l, v_l, kv_valid=m_l, **kw)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(qspec, kvspec, kvspec, mspec),
                         out_specs=qspec, check_vma=False)(q, k, v, kv_valid)


def paged_decode_restriction(q_shape, pages_shape, dtype) -> Optional[str]:
    """Why the split-KV kernel cannot take this paged decode call — None when
    it can.  Shape-static, so routing never recompiles the decode block."""
    if len(q_shape) != 5 or len(pages_shape) != 4:
        return (f"unexpected layout q{tuple(q_shape)} / pages"
                f"{tuple(pages_shape)} (want (B,1,KV,G,hd) / (N,ps,KV,hd))")
    if q_shape[1] != 1:
        return f"decode expects a single query position, got S={q_shape[1]}"
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return f"non-float dtype {jnp.dtype(dtype).name}"
    hd, ps = q_shape[-1], pages_shape[1]
    if hd > MAX_FLASH_HEAD_DIM:
        return (f"head_dim {hd} exceeds the kernel VMEM tile budget "
                f"({MAX_FLASH_HEAD_DIM})")
    if hd % 8 != 0:
        return f"head_dim {hd} not a multiple of the 8-sublane layout"
    if ps % 8 != 0:
        return f"page_size {ps} not a multiple of the 8-sublane layout"
    return None


def paged_decode_ok(q, k_pages, backend: KernelBackend) -> bool:
    """Dispatch predicate for one paged decode-attention call; warns once per
    reason when pallas was forced but the call falls back to the jnp gather."""
    if not backend.use_pallas:
        return False
    reason = paged_decode_restriction(q.shape, k_pages.shape, q.dtype)
    if reason is not None:
        _warn_forced_attention_fallback(backend, reason)
        return False
    return True


def fused_paged_decode(q, k_pages, v_pages, page_table, valid_count, *,
                       backend: KernelBackend, pages_per_split: int = 0):
    """The split-KV paged decode kernel, shard_map-wrapped under a mesh.

    Decode attention is independent per (slot, KV head): q/page_table/
    valid_count shard on batch -> data, the page pool on kv_heads -> model
    (each data shard keeps a full pool replica for its slots — the pool has
    no batch dim).  Axes that don't divide are dropped by ``logical_to_spec``
    exactly as in :func:`fused_flash_attention`.
    """
    kw = dict(pages_per_split=pages_per_split, interpret=backend.interpret)
    if not backend.sharded:
        return paged_decode_attention(q, k_pages, v_pages, page_table,
                                      valid_count, **kw)
    mesh = backend.mesh
    rules = active_rules()
    qspec = logical_to_spec(ATTN_Q_AXES, shape=q.shape, mesh=mesh, rules=rules)
    pspec = logical_to_spec(PAGED_POOL_AXES, shape=k_pages.shape, mesh=mesh,
                            rules=rules)
    tspec = logical_to_spec(PAGED_TABLE_AXES, shape=page_table.shape,
                            mesh=mesh, rules=rules)
    vspec = logical_to_spec(("batch",), shape=valid_count.shape, mesh=mesh,
                            rules=rules)

    def local(q_l, k_l, v_l, t_l, c_l):
        return paged_decode_attention(q_l, k_l, v_l, t_l, c_l, **kw)

    return jax.shard_map(local, mesh=mesh,
                         in_specs=(qspec, pspec, pspec, tspec, vspec),
                         out_specs=qspec, check_vma=False)(
                             q, k_pages, v_pages, page_table, valid_count)


def moments_fusable(m, v, p, optimizer: str) -> bool:
    """Tier-1 placeholder moments (1-element stubs) cannot stream through the
    kernels — but those leaves are statically frozen and never reach the fused
    path anyway; this guards the dispatch decision."""
    if m.shape != p.shape:
        return False
    if optimizer != "sgd" and v.shape != p.shape:
        return False
    return True
