"""Logical-axis sharding (MaxText-style).

Model code annotates tensors with *logical* axis names ("batch", "ffn", "expert",
…).  A :class:`ShardingRules` table maps logical names to mesh axes; resolution
checks divisibility and silently drops a mapping when the dimension does not divide
the mesh axis (e.g. mixtral's 8 experts on a 16-way model axis), so one rule table
serves every architecture.

``use_mesh(mesh, rules)`` installs a process-global context; ``logical_constraint``
is a no-op outside it, so single-device unit tests run the exact same model code.

Every mesh axis is ``Auto`` (GSPMD propagates shardings from the constraints).
``jax.make_mesh`` now defaults to explicit axes, under which JAX refuses those
constraints and the embedding gather, so meshes come from :func:`make_mesh`
and ``use_mesh`` refuses any other kind.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

Logical = Union[str, None, Tuple[str, ...]]


@dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (or tuple of mesh axes)."""

    table: Mapping[str, Union[str, Tuple[str, ...]]] = field(default_factory=dict)

    def resolve(self, name: Logical) -> Union[str, Tuple[str, ...], None]:
        if name is None:
            return None
        if isinstance(name, tuple):  # pre-resolved tuple of logical names
            out = []
            for n in name:
                r = self.resolve(n)
                if r is None:
                    continue
                out.extend(r if isinstance(r, tuple) else (r,))
            return tuple(out) or None
        return self.table.get(name)


#: Default 2-D (data, model) rules; the dry-run adds "pod" to the batch/fsdp axes.
DEFAULT_RULES = ShardingRules(table={
    "batch": ("data",),
    "fsdp": ("data",),          # weight d_model dim (ZeRO-3 style)
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "qdim": ("model",),         # fused heads*head_dim projection dim
    "kvdim": ("model",),
    "ffn": ("model",),
    "expert": ("model",),
    "ssm_inner": ("model",),
    "attn_seq": ("model",),
})

MULTIPOD_RULES = ShardingRules(table={
    **DEFAULT_RULES.table,
    "batch": ("pod", "data"),
    "fsdp": ("data",),
})

#: Weight-stationary decode rules (§Perf iteration 2): at decode the activations
#: are tiny and the weights dominate — FSDP-style output/row sharding forces an
#: all-gather of every weight matrix per step.  Instead shard every weight on its
#: CONTRACTION (input) dim across the whole chip grid: matmuls produce partial
#: activations reduced with a small psum, and no weight ever moves.
DECODE_RULES = ShardingRules(table={
    "batch": ("data",),
    "fsdp": ("data", "model"),
    "attn_seq": ("model",),
})

MULTIPOD_DECODE_RULES = ShardingRules(table={
    **DECODE_RULES.table,
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data", "model"),
})


#: Logical axes of the attention activations in the model layout
#: (``models/attention.py``): q ``(B, S, KV, G, hd)``, k/v ``(B, T, KV, hd)``,
#: kv-valid mask ``(B, T)``.  Attention is independent per (batch row, KV
#: head), so these are exactly the axes the kernel dispatch layer shard_maps
#: the flash kernels over (``kernels/dispatch.py``); ``launch/specs.py`` uses
#: the same tuples for the serve-cell KV-cache shardings (with a leading layer
#: axis), so the kernel always sees the layout the cache actually has.
ATTN_Q_AXES: Tuple[Logical, ...] = ("batch", None, "kv_heads", None, None)
ATTN_KV_AXES: Tuple[Logical, ...] = ("batch", None, "kv_heads", None)
ATTN_MASK_AXES: Tuple[Logical, ...] = ("batch", None)


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: ShardingRules = DEFAULT_RULES


_ctx = _Ctx()


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """A device mesh with every axis ``Auto`` (see the module docstring);
    ``devices`` defaults to all of ``jax.devices()``."""
    devices = jax.devices() if devices is None else devices
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, rules: Optional[ShardingRules] = None):
    if any(t != AxisType.Auto for t in mesh.axis_types):
        raise ValueError(
            f"use_mesh needs Auto mesh axes, got {mesh.axis_types}: build "
            f"the mesh with repro.distributed.sharding.make_mesh")
    prev = (_ctx.mesh, _ctx.rules)
    _ctx.mesh = mesh
    _ctx.rules = rules or (_ctx.rules or DEFAULT_RULES)
    try:
        with mesh:
            yield mesh
    finally:
        _ctx.mesh, _ctx.rules = prev


@contextlib.contextmanager
def suspend_mesh():
    """Temporarily clear the logical-sharding context (thread-local).

    Used at trace time around code running inside a *manual* ``shard_map``
    body (the explicit-reduce step, ``distributed/reduce.py``): there every
    mesh axis is already manual, and ``logical_constraint``'s
    ``with_sharding_constraint`` would be rejected by XLA ("axis ... is also
    found in manual_axes").  Inside the suspension the constraints degrade to
    the same no-op they are on a single device."""
    prev = (_ctx.mesh, _ctx.rules)
    _ctx.mesh, _ctx.rules = None, _ctx.rules
    try:
        yield
    finally:
        _ctx.mesh, _ctx.rules = prev


def active_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def active_rules() -> ShardingRules:
    return _ctx.rules


def mesh_axis_size(mesh: Mesh, axes: Union[str, Tuple[str, ...], None]) -> int:
    """Product of the named mesh-axis extents (``None`` -> 1).

    The one place the ``axis name -> extent`` view of a mesh is built; shared by
    ``logical_to_spec``'s divisibility check and the shard_map kernel dispatch
    (``kernels/dispatch.py``) so both agree on what a mapping shards over.
    """
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


def model_axis_size(mesh: Mesh) -> int:
    """Extent of the tensor-parallel "model" axis (1 when the mesh lacks one)."""
    return mesh_axis_size(mesh, "model") if "model" in mesh.axis_names else 1


def logical_to_spec(logical_axes: Sequence[Logical],
                    shape: Optional[Sequence[int]] = None,
                    mesh: Optional[Mesh] = None,
                    rules: Optional[ShardingRules] = None) -> P:
    """Resolve logical axes to a PartitionSpec, dropping non-dividing mappings."""
    mesh = mesh or _ctx.mesh
    rules = rules or _ctx.rules
    out = []
    for i, name in enumerate(logical_axes):
        resolved = rules.resolve(name)
        if resolved is not None and shape is not None and mesh is not None:
            if shape[i] % mesh_axis_size(mesh, resolved) != 0:
                resolved = None
        out.append(resolved)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_constraint(x, logical_axes: Sequence[Logical]):
    if _ctx.mesh is None:
        return x
    spec = logical_to_spec(logical_axes, shape=x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_ctx.mesh, spec))


def named_sharding(logical_axes: Sequence[Logical], shape: Sequence[int],
                   mesh: Optional[Mesh] = None,
                   rules: Optional[ShardingRules] = None) -> NamedSharding:
    mesh = mesh or _ctx.mesh
    assert mesh is not None, "named_sharding requires a mesh"
    return NamedSharding(mesh, logical_to_spec(logical_axes, shape, mesh, rules))


def param_partition_specs(params, logical_axes,
                          mesh: Optional[Mesh] = None,
                          rules: Optional[ShardingRules] = None
                          ) -> Dict[Tuple[str, ...], P]:
    """Per-parameter ``PartitionSpec``s keyed by tree path.

    Resolves each leaf of ``logical_axes`` (the ``model.param_logical_axes``
    tree, a prefix structure of ``params``) against the mesh with the same
    divisibility rule as ``logical_to_spec``.  This is the spec tree the kernel
    dispatch layer threads down to its ``shard_map`` wrappers — the same
    resolution the launcher uses for state shardings (``launch/specs.py``), so
    the kernels always see the layout the data actually has.

    ``params`` may hold arrays or ``ShapeDtypeStruct``s (only ``.shape`` is
    read); paths use the same string keys as ``core.grades``.
    """
    from repro.core.grades import _key_path  # one path-key derivation everywhere

    mesh = mesh or _ctx.mesh
    rules = rules or _ctx.rules
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    axes_leaves = treedef.flatten_up_to(logical_axes)
    out: Dict[Tuple[str, ...], P] = {}
    for (kp, leaf), ax in zip(flat, axes_leaves):
        out[_key_path(kp)] = logical_to_spec(ax, shape=leaf.shape, mesh=mesh,
                                             rules=rules)
    return out
