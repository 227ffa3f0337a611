"""Pallas TPU kernel: fused GradES monitor op (paper Eq. 1), freeze-gated.

Computes, for a stacked gradient tensor ``g (L, M, N)``, the stored previous
gradient ``prev (L, M, N)`` and per-layer freeze flags ``frozen (L,)``::

    norm[l]  = sum_{ij} | g[l] - prev[l] |   if not frozen[l] else 0
    prev'[l] = g[l]                          if not frozen[l] else prev[l]

in ONE pass: the unfused jnp version reads g and prev to form ``|g-prev|``, reads
the temporary to reduce it, and writes prev' separately — ≥4 HBM passes over the
gradient bytes; this kernel does 2 reads + 1 write (the roofline minimum) with the
partial L1 accumulated in VMEM across the N-tile loop.

Freezing is permanent (GradES monotonicity), so a frozen layer's monitor value
can never un-freeze it — its 2 reads + 1 ``prev`` write-back are pure waste.
The flags ride whole in SMEM exactly like ``masked_adamw``'s, so the predicate
is a scalar load and a frozen layer costs no vector work; ``input_output_aliases`` pins ``prev'``
onto ``prev`` so the frozen copy-through is a no-op store on hardware (the
explicit copy is required for interpret-mode correctness).

Grid: (L, M/bm, N/bn), sequential on TPU, so the accumulator block for layer
``l`` is initialized at the first (i,j) tile and accumulated in place after.
The per-layer norm is kept as an ``(L, 1, 128)`` lane row (every lane holds the
same sum): a ``(1, 1, 128)`` block satisfies the TPU tiling rule, which a
``(1, 1)`` block of an ``(L, 1)`` array does not.
Block shapes default to (256, 512) — 512 KiB of bf16 per input tile, comfortably
inside the ~16 MiB VMEM budget with double buffering, and both dims are multiples
of the 8×128 VREG lane layout.

Under a sharded mesh this kernel runs once per shard (shard_map in
``kernels/dispatch.py``) over the *local* (L, M, N): the returned ``norm`` is
then a partial sum over the shard's trailing elements, and the dispatch layer
psums partials over the mesh axes that shard trailing dims to recover Eq. 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: lane width of the per-layer norm row (one TPU vreg row)
_LANES = 128


def _kernel(flags_ref, g_ref, prev_ref, norm_ref, newprev_ref):
    l = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    live = flags_ref[l] == 0

    @pl.when((i == 0) & (j == 0))
    def _init():
        norm_ref[...] = jnp.zeros_like(norm_ref)

    @pl.when(live)
    def _update():
        g = g_ref[0]
        delta = (g.astype(jnp.float32) - prev_ref[0].astype(jnp.float32))
        norm_ref[...] += jnp.sum(jnp.abs(delta))
        newprev_ref[0] = g.astype(newprev_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        # Copy-through: a no-op store under input/output aliasing on TPU;
        # interpret mode needs the explicit write.
        newprev_ref[0] = prev_ref[0]


def grades_norm_kernel(g, prev, frozen=None, *, block_m: int = 256,
                       block_n: int = 512, interpret=None):
    """g, prev: (L, M, N); frozen: (L,) bool/int or None (all live)
    -> (norm (L,), new_prev (L, M, N)).  ``interpret`` None = derived from
    the backend (:func:`repro.kernels.resolve_interpret`)."""
    L, M, N = g.shape
    flags = (jnp.zeros((L,), jnp.int32) if frozen is None
             else frozen.astype(jnp.int32))
    bm, bn = min(block_m, M), min(block_n, N)
    # pad-free requirement: tests sweep ragged shapes via the ops-level wrapper
    assert M % bm == 0 and N % bn == 0, (g.shape, bm, bn)
    grid = (L, M // bm, N // bn)
    norm, new_prev = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # flags: whole, scalars
            pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j)),
            pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, _LANES), lambda l, i, j: (l, 0, 0)),
            pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((L, 1, _LANES), jnp.float32),
            jax.ShapeDtypeStruct(g.shape, prev.dtype),
        ],
        input_output_aliases={2: 1},
        interpret=resolve_interpret(interpret),
        name="grades_norm",
    )(flags, g, prev)
    return norm[:, 0, 0], new_prev
