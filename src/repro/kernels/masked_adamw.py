"""Pallas TPU kernels: frozen-gated fused optimizer updates (GradES Tier 0).

For a stacked parameter ``p (L, M, N)`` with per-layer freeze flags
``frozen (L,)``, performs the AdamW (or SGD-momentum) update for live layers
and *skips all compute and writes* for frozen layers (``pl.when`` predication
on the flag): a frozen layer costs one flag load instead of the full
p/m/v/g read-modify-write — an 8·bytes/param HBM-traffic saving that the jnp
``where``-based update cannot express (XLA still streams all four operands).

All step-varying hyperparameters (lr, bias-correction terms) ride in a single
dynamic ``hyper`` f32 vector, so a learning-rate schedule never forces a
recompile; ``input_output_aliases`` pins p/m/v outputs onto their inputs so the
frozen-branch copy-through is a true no-op write on TPU (the explicit copies
below are required for interpret-mode correctness and are elided under
aliasing on hardware).

Grid (L, M/bm, N/bn); flags and hyper ride whole in SMEM, so the predicate
and the hyperparameters are scalar loads (a TPU kernel may load only from
VMEM or SMEM references).

The update is elementwise, so under a sharded mesh the kernel body runs
unchanged per shard (shard_map in ``kernels/dispatch.py``); ``frozen`` then
holds the rows of this shard only — the dispatch layer slices the replicated
global flags by the device's coordinates along the granularity mesh axes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: layout of the dynamic hyper operand (f32 vector)
HYPER_LEN = 7  # [lr, b1, b2, eps, weight_decay, 1-b1**t, 1-b2**t]


def _adamw_body(flags_ref, hyper_ref, p_ref, g_ref, m_ref, v_ref,
                p_out, m_out, v_out):
    l = pl.program_id(0)
    live = flags_ref[l] == 0

    @pl.when(live)
    def _update():
        lr, b1, b2, eps, wd, c1, c2 = (hyper_ref[k] for k in range(HYPER_LEN))
        g = g_ref[0].astype(jnp.float32)
        m = b1 * m_ref[0].astype(jnp.float32) + (1.0 - b1) * g
        v = b2 * v_ref[0].astype(jnp.float32) + (1.0 - b2) * g * g
        mhat = m / c1
        vhat = v / c2
        p = p_ref[0].astype(jnp.float32)
        p = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
        p_out[0] = p.astype(p_out.dtype)
        m_out[0] = m.astype(m_out.dtype)
        v_out[0] = v.astype(v_out.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        # Copy-through: a no-op store under input/output aliasing on TPU;
        # interpret mode needs the explicit writes.
        p_out[0] = p_ref[0]
        m_out[0] = m_ref[0]
        v_out[0] = v_ref[0]


def _sgd_body(flags_ref, hyper_ref, p_ref, g_ref, m_ref, p_out, m_out):
    l = pl.program_id(0)
    live = flags_ref[l] == 0

    @pl.when(live)
    def _update():
        lr, b1, wd = hyper_ref[0], hyper_ref[1], hyper_ref[4]
        g = g_ref[0].astype(jnp.float32)
        m = b1 * m_ref[0].astype(jnp.float32) + g
        p = p_ref[0].astype(jnp.float32)
        p = p - lr * (m + wd * p)
        p_out[0] = p.astype(p_out.dtype)
        m_out[0] = m.astype(m_out.dtype)

    @pl.when(jnp.logical_not(live))
    def _skip():
        p_out[0] = p_ref[0]
        m_out[0] = m_ref[0]


def _blocked(body, p, operands, n_state: int, block_m: int, block_n: int,
             interpret, name: str):
    """Shared pallas_call plumbing: (flags, hyper, p, g, state...) ->
    (p', state'...); the mutable operands alias their outputs."""
    L, M, N = p.shape
    bm, bn = min(block_m, M), min(block_n, N)
    assert M % bm == 0 and N % bn == 0, (p.shape, bm, bn)
    grid = (L, M // bm, N // bn)
    spec = pl.BlockSpec((1, bm, bn), lambda l, i, j: (l, i, j))
    n_tensor = 2 + n_state  # p, g, then moments
    mutable = [2] + list(range(4, 4 + n_state))  # input idx of p, m[, v]
    outs = [operands[k] for k in mutable]        # (p, m[, v])
    return pl.pallas_call(
        body,
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # flags: whole
                pl.BlockSpec(memory_space=pltpu.SMEM),  # hyper
            ] + [spec] * n_tensor,
            out_specs=[spec] * (1 + n_state),
        ),
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs],
        input_output_aliases={inp: out for out, inp in enumerate(mutable)},
        interpret=resolve_interpret(interpret),
        name=name,
    )(*operands)


def masked_adamw_kernel(p, g, m, v, frozen, hyper, *, block_m: int = 256,
                        block_n: int = 512, interpret=None):
    """p,g,m,v: (L, M, N); frozen: (L,) bool/int; hyper: (7,) f32 dynamic
    vector ``[lr, b1, b2, eps, wd, 1-b1**t, 1-b2**t]``. Returns (p', m', v')."""
    flags = frozen.astype(jnp.int32)
    hyper = jnp.asarray(hyper, jnp.float32)
    return _blocked(_adamw_body, p, (flags, hyper, p, g, m, v), 2,
                    block_m, block_n, interpret, "masked_adamw")


def masked_sgd_kernel(p, g, m, frozen, hyper, *, block_m: int = 256,
                      block_n: int = 512, interpret=None):
    """SGD-momentum variant: p,g,m: (L, M, N). Returns (p', m')."""
    flags = frozen.astype(jnp.int32)
    hyper = jnp.asarray(hyper, jnp.float32)
    return _blocked(_sgd_body, p, (flags, hyper, p, g, m), 1,
                    block_m, block_n, interpret, "masked_sgd")
