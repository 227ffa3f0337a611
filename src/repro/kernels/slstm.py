"""Pallas TPU kernel: sLSTM recurrence with VMEM-resident recurrent weights.

The sLSTM time scan is the worst memory offender in the zoo (EXPERIMENTS.md
§Perf iteration 3): in plain XLA each of the T sequential steps re-reads the
recurrent matrices R (4 gates × H heads × hd×hd) from HBM — at xlstm-350m
train_4k that is ~100 TB/step of pure weight re-reads.  R is only ~2 MiB per
layer, so the xLSTM authors' own CUDA kernel keeps it in SRAM; the TPU analogue
is this Pallas kernel:

* grid = (B/bB, T/chunk), sequential on TPU.  R's index_map is constant, so the
  pipeline fetches it into VMEM once and revisits the same buffer every step.
* per-(batch-block) state (h, c, n, m — each (bB, D) f32) lives in VMEM scratch,
  initialized at t==0 and carried across the whole T loop without HBM round
  trips; the final state is emitted for decode handoff.
* the only HBM streaming is x_proj in (bB, chunk, 4D) and h out (bB, chunk, D) —
  the roofline minimum.

hd is padded to the 128-lane layout by the ops wrapper when needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(xp_ref, r_ref, h0_ref, c0_ref, n0_ref, m0_ref,
            hseq_ref, hT_ref, cT_ref, nT_ref, mT_ref,
            h_s, c_s, n_s, m_s, *, chunk: int, n_heads: int):
    t = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(t == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)
        c_s[...] = c0_ref[...].astype(jnp.float32)
        n_s[...] = n0_ref[...].astype(jnp.float32)
        m_s[...] = m0_ref[...].astype(jnp.float32)

    r = r_ref[...]                                   # (4, H, hd, hd) — VMEM hot
    bB = xp_ref.shape[0]
    D4 = xp_ref.shape[-1]
    D = D4 // 4
    hd = D // n_heads

    def step(i, _):
        xp = xp_ref[:, 0, i, :].astype(jnp.float32)  # (bB, 4D)
        h = h_s[...]
        hh = h.reshape(bB, n_heads, hd).astype(r.dtype)
        # rec[g] = h @ R[g]  per head  -> (4, bB, D)
        rec = jax.lax.dot_general(
            hh.transpose(1, 0, 2),                   # (H, bB, hd_k)
            r.transpose(1, 2, 0, 3).reshape(n_heads, hd, 4 * hd),  # (H, hd_k, 4*hd_j)
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # (H, bB, 4*hd)
        rec = rec.reshape(n_heads, bB, 4, hd).transpose(2, 1, 0, 3).reshape(4, bB, D)
        zr = xp[:, 0 * D:1 * D] + rec[0]
        ir = xp[:, 1 * D:2 * D] + rec[1]
        fr = xp[:, 2 * D:3 * D] + rec[2]
        orr = xp[:, 3 * D:4 * D] + rec[3]
        zt = jnp.tanh(zr)
        ot = jax.nn.sigmoid(orr)
        flog = jax.nn.log_sigmoid(fr)
        m_new = jnp.maximum(flog + m_s[...], ir)
        fw = jnp.exp(flog + m_s[...] - m_new)
        iw = jnp.exp(ir - m_new)
        c = fw * c_s[...] + iw * zt
        n = fw * n_s[...] + iw
        h_new = ot * c / jnp.maximum(n, 1.0)
        h_s[...] = h_new
        c_s[...] = c
        n_s[...] = n
        m_s[...] = m_new
        hseq_ref[:, 0, i, :] = h_new.astype(hseq_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(t == nt - 1)
    def _final():
        hT_ref[...] = h_s[...]
        cT_ref[...] = c_s[...]
        nT_ref[...] = n_s[...]
        mT_ref[...] = m_s[...]


def slstm_kernel(x_proj, r, h0, c0, n0, m0, *, n_heads: int, chunk: int = 128,
                 block_b: int = 0, interpret=None):
    """x_proj: (B, T, 4D); r: (4, H, hd, hd); states (B, D) f32.
    ``interpret`` None = derived from the backend
    (:func:`repro.kernels.resolve_interpret`).

    Returns (h_seq (B, T, D), h_T, c_T, n_T, m_T)."""
    B, T, D4 = x_proj.shape
    D = D4 // 4
    chunk = min(chunk, T)
    assert T % chunk == 0
    bB = block_b or B
    assert B % bB == 0
    grid = (B // bB, T // chunk)
    xp3 = x_proj.reshape(B, T // chunk, chunk, D4)

    state_spec = pl.BlockSpec((bB, D), lambda b, t: (b, 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, n_heads=n_heads),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bB, 1, chunk, D4), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec(r.shape, lambda b, t: (0, 0, 0, 0)),  # VMEM-resident
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_specs=[
            pl.BlockSpec((bB, 1, chunk, D), lambda b, t: (b, t, 0, 0)),
            state_spec, state_spec, state_spec, state_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T // chunk, chunk, D), x_proj.dtype),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bB, D), jnp.float32) for _ in range(4)],
        interpret=resolve_interpret(interpret),
        name="slstm",
    )(xp3.reshape(B, T // chunk, chunk, D4)[:, :, :, :],
      r, h0, c0, n0, m0)
    h_seq = outs[0].reshape(B, T, D)
    return (h_seq,) + tuple(outs[1:])
