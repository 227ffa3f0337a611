"""Pallas TPU kernels: flash attention, forward AND backward (custom_vjp).

This is the production attention path (DESIGN.md §3b): the TPU-native version
of the blockwise online-softmax schedule in ``models/attention.py``, now
covering everything ``attention()`` actually uses:

* **GQA-native layout** — callers pass ``q (B, S, KV, G, hd)`` / ``k, v
  (B, T, KV, hd)`` (the model-layer layout); the wrapper re-lays q into
  per-KV-head row blocks ``(B, KV, G·S, hd)`` so grouped query heads share
  their KV tile in VMEM without ever materializing repeated K/V in HBM.
  ``v`` may carry a head dim of its own (latent attention: q·k over 192,
  P·V over 128); the output, ``dO`` and ``dV`` tiles take v's.
* **Masking** — causal, sliding ``window``, and a ``kv_valid (B, T)`` mask
  (padded cache slots / ragged lengths), all applied in-kernel with the shared
  ``masking.NEG_INF`` constant so parity tests compare identical semantics.
* **Non-block-multiple shapes** — S and T are padded up to the tile grid and
  sliced back; padded KV columns are masked, padded query rows carry zero
  cotangents, so both directions are exact.
* **Backward kernels** — the forward saves ``(o, logsumexp)`` residuals; the
  backward recomputes score tiles (no (S×T) tensor in HBM in either direction)
  in two passes: ``dq`` accumulates over KV tiles on the forward grid, and
  ``dk/dv`` accumulate over query-row tiles on the transposed grid (the row
  loop also sums over the G query groups of each KV head — exactly the GQA
  reduction).  ``jax.custom_vjp`` wires them under ``jax.grad``.

Grid (fwd / dq): (B, KV, R/bq, T/bk) with R = G·S_padded; the innermost KV
tile loop is sequential so running (m, l, acc) live in VMEM scratch.  Tiles
are (bq, hd)/(bk, hd) slabs — multiples of the 8×128 VREG layout for the
default 256×256 blocks.  Every block's last two dims obey the TPU tiling rule
(each divisible by 8 / 128 or equal to the array's own): the kv-valid mask is
laid out ``(B, 1, Tp)`` and read as ``(1, bk)`` rows, and the per-row
logsumexp / ``D`` residuals are ``(B, KV, R, 1)`` columns read as ``(bq, 1)``.
Causal/window tiles that cannot contribute are predicated off with
``pl.when`` on the tile's row offset.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.masking import (NEG_INF, band_live, rows_alive,
                                   zero_dead_rows)


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _tile_geometry(S: int, T: int, block_q: int, block_k: int):
    """(bq, Sp, bk, Tp): block sizes and padded extents.  Sp % bq == 0 so row
    blocks never straddle a query-group boundary in the (G·Sp) row layout."""
    bq = min(block_q, round_up(S, 8))
    Sp = round_up(S, bq)
    bk = min(block_k, round_up(T, 128 if T >= 128 else 8))
    Tp = round_up(T, bk)
    return bq, Sp, bk, Tp


# ---------------------------------------------------------------------------
# Layout: (B, S, KV, G, hd) <-> per-KV-head row blocks (B, KV, G*Sp, hd)
# ---------------------------------------------------------------------------

def _q_to_rows(q, Sp: int):
    """(B, S, KV, G, hd) -> (B, KV, G*Sp, hd); rows of group g occupy
    [g*Sp, (g+1)*Sp), so row r has sequence position (r % Sp)."""
    B, S, KV, G, hd = q.shape
    qt = q.transpose(0, 2, 3, 1, 4)                      # (B, KV, G, S, hd)
    if Sp != S:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, Sp - S), (0, 0)))
    return qt.reshape(B, KV, G * Sp, hd)


def _rows_to_q(x, S: int, G: int):
    """Inverse of :func:`_q_to_rows` (slices padding off)."""
    B, KV, R, hd = x.shape
    Sp = R // G
    x = x.reshape(B, KV, G, Sp, hd)[:, :, :, :S]
    return x.transpose(0, 3, 1, 2, 4)


def _kv_to_rows(k, Tp: int):
    """(B, T, KV, hd) -> (B, KV, Tp, hd)."""
    kt = k.transpose(0, 2, 1, 3)
    T = k.shape[1]
    if Tp != T:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, Tp - T), (0, 0)))
    return kt


def _rows_to_kv(kt, T: int):
    return kt[:, :, :T].transpose(0, 2, 1, 3)


def _mask_rows(mask, Tp: int):
    """(B, T) kv-valid gate -> (B, 1, Tp), padded columns masked."""
    T = mask.shape[1]
    if Tp != T:
        mask = jnp.pad(mask, ((0, 0), (0, Tp - T)))
    return mask[:, None, :]


# ---------------------------------------------------------------------------
# In-kernel masking (shared by forward and both backward kernels)
# ---------------------------------------------------------------------------

def _tile_live(off, kj, *, bq: int, bk: int, causal: bool, window: int):
    """Whether the (row-offset ``off``, kv tile ``kj``) score tile can
    contribute at all — tiles fully outside the shared causal/window band
    (``masking.band_live``) are predicated off with ``pl.when``."""
    return band_live(off, bq, kj * bk, bk, causal=causal, window=window)


def _mask_tile(s, off, col0, mask_row, *, causal: bool, window: int):
    """Apply kv-valid/padding + causal + window masks to one (bq, bk) tile.
    ``off`` is the sequence position of the tile's first row, ``col0`` of its
    first column; ``mask_row (1, bk)`` is the f32 0/1 kv-valid slice."""
    rows = off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = mask_row > 0.0
    if causal:
        ok = jnp.logical_and(ok, cols <= rows)
    if window:
        ok = jnp.logical_and(ok, cols > rows - window)
    return jnp.where(ok, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(mask_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                bq: int, bk: int, Sp: int, causal: bool, window: int,
                scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    off = (qi * bq) % Sp  # sequence position of this tile's first query row

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(_tile_live(off, kj, bq=bq, bk=bk, causal=causal, window=window))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = _mask_tile(s, off, kj * bk, mask_ref[0], causal=causal,
                       window=window)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[0, 0],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def _forward(q, k, v, mask, *, causal: bool, window: int, block_q: int,
             block_k: int, interpret: bool):
    """Returns (o external layout, (o_rows, lse) residuals in row layout)."""
    B, S, KV, G, hd = q.shape
    T, hdv = k.shape[1], v.shape[-1]
    bq, Sp, bk, Tp = _tile_geometry(S, T, block_q, block_k)
    R = G * Sp
    qr = _q_to_rows(q, Sp)
    kr = _kv_to_rows(k, Tp)
    vr = _kv_to_rows(v, Tp)
    mp = _mask_rows(mask, Tp)
    grid = (B, KV, R // bq, Tp // bk)
    kernel = functools.partial(_fwd_kernel, bq=bq, bk=bk, Sp=Sp,
                               causal=causal, window=window,
                               scale=hd ** -0.5)
    o_rows, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hdv), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hdv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, R, hdv), q.dtype),
            jax.ShapeDtypeStruct((B, KV, R, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, hdv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(mp, qr, kr, vr)
    return _rows_to_q(o_rows, S, G), (o_rows, lse)


# ---------------------------------------------------------------------------
# Backward kernels (score tiles recomputed from q/k + saved lse)
# ---------------------------------------------------------------------------

def _dq_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, acc_ref, *,
               bq: int, bk: int, Sp: int, causal: bool, window: int,
               scale: float):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    off = (qi * bq) % Sp

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_tile_live(off, kj, bq=bq, bk=bk, causal=causal, window=window))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = _mask_tile(s, off, kj * bk, mask_ref[0], causal=causal,
                       window=window)
        p = jnp.exp(s - lse_ref[0, 0])                          # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta_ref[0, 0])
        acc_ref[...] += jax.lax.dot(ds, k) * scale

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0, 0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(mask_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                bq: int, bk: int, Sp: int, causal: bool, window: int,
                scale: float):
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)
    off = (qi * bq) % Sp

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(_tile_live(off, kj, bq=bq, bk=bk, causal=causal, window=window))
    def _tile():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = _mask_tile(s, off, kj * bk, mask_ref[0], causal=causal,
                       window=window)
        p = jnp.exp(s - lse_ref[0, 0])                          # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta_ref[0, 0])
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _backward(q, k, v, mask, o_rows, lse, do, *, causal: bool, window: int,
              block_q: int, block_k: int, interpret: bool):
    B, S, KV, G, hd = q.shape
    T, hdv = k.shape[1], v.shape[-1]
    bq, Sp, bk, Tp = _tile_geometry(S, T, block_q, block_k)
    R = G * Sp
    qr = _q_to_rows(q, Sp)
    kr = _kv_to_rows(k, Tp)
    vr = _kv_to_rows(v, Tp)
    dor = _q_to_rows(do, Sp)  # padded rows carry zero cotangents
    mp = _mask_rows(mask, Tp)
    # D_i = sum_d dO_i·O_i — one elementwise pass, shared by both kernels.
    delta = jnp.sum(dor.astype(jnp.float32) * o_rows.astype(jnp.float32),
                    axis=-1, keepdims=True)
    kw = dict(bq=bq, bk=bk, Sp=Sp, causal=causal, window=window,
              scale=hd ** -0.5)

    mask_spec = pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j))
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0))
    o_spec = pl.BlockSpec((1, 1, bq, hdv), lambda b, h, i, j: (b, h, i, 0))
    k_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0))
    v_spec = pl.BlockSpec((1, 1, bk, hdv), lambda b, h, i, j: (b, h, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    dqr = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=(B, KV, R // bq, Tp // bk),
        in_specs=[mask_spec, q_spec, k_spec, v_spec, o_spec, row_spec,
                  row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
        name="flash_dq",
    )(mp, qr, kr, vr, dor, lse, delta)

    # Transposed grid: the sequential inner loop walks ALL G·Sp query rows of
    # this KV head, accumulating the GQA group reduction into dk/dv.
    t_mask = pl.BlockSpec((1, 1, bk), lambda b, h, j, i: (b, 0, j))
    t_q = pl.BlockSpec((1, 1, bq, hd), lambda b, h, j, i: (b, h, i, 0))
    t_o = pl.BlockSpec((1, 1, bq, hdv), lambda b, h, j, i: (b, h, i, 0))
    t_k = pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0))
    t_v = pl.BlockSpec((1, 1, bk, hdv), lambda b, h, j, i: (b, h, j, 0))
    t_row = pl.BlockSpec((1, 1, bq, 1), lambda b, h, j, i: (b, h, i, 0))
    dkr, dvr = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=(B, KV, Tp // bk, R // bq),
        in_specs=[t_mask, t_q, t_k, t_v, t_o, t_row, t_row],
        out_specs=[t_k, t_v],
        out_shape=[jax.ShapeDtypeStruct((B, KV, Tp, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, KV, Tp, hdv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hdv), jnp.float32)],
        interpret=interpret,
        name="flash_dkv",
    )(mp, qr, kr, vr, dor, lse, delta)

    dq = _rows_to_q(dqr, S, G).astype(q.dtype)
    dk = _rows_to_kv(dkr, T).astype(k.dtype)
    dv = _rows_to_kv(dvr, T).astype(v.dtype)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wiring + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash(causal, window, block_q, block_k, interpret, q, k, v, mask):
    o, _ = _forward(q, k, v, mask, causal=causal, window=window,
                    block_q=block_q, block_k=block_k, interpret=interpret)
    return o


def _flash_fwd(causal, window, block_q, block_k, interpret, q, k, v, mask):
    o, (o_rows, lse) = _forward(q, k, v, mask, causal=causal, window=window,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)
    return o, (q, k, v, mask, o_rows, lse)


def _flash_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, mask, o_rows, lse = res
    dq, dk, dv = _backward(q, k, v, mask, o_rows, lse, do, causal=causal,
                           window=window, block_q=block_q, block_k=block_k,
                           interpret=interpret)
    # mask is a 0/1 f32 gate derived from integer validity — no useful grad.
    return dq, dk, dv, jnp.zeros_like(mask)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    kv_valid=None, block_q: int = 256, block_k: int = 256,
                    interpret=None):
    """Flash attention in the model layout, differentiable end to end.

    q, k: (B, S, KV, G, hd), (B, T, KV, hd); v: (B, T, KV, hdv), whose head
    dim may differ from the query/key one (latent attention: q·k over 192,
    P·V over 128); scores are scaled by ``hd ** -0.5``.  kv_valid: optional
    (B, T) bool/0-1 validity mask.  Returns (B, S, KV, G, hdv).  Matches
    ``models.attention.full_attention`` (and its gradients) for causal,
    windowed, GQA, and padded-length cases; S/T need not be block multiples.
    ``interpret`` None = derived from the backend
    (:func:`repro.kernels.resolve_interpret`).
    """
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    assert k.shape == (B, T, KV, hd) and v.shape[:3] == (B, T, KV), \
        (q.shape, k.shape, v.shape)
    mask = (jnp.ones((B, T), jnp.float32) if kv_valid is None
            else kv_valid.astype(jnp.float32))
    out = _flash(bool(causal), int(window), int(block_q), int(block_k),
                 resolve_interpret(interpret), q, k, v, mask)
    # Rows with no visible valid key get exactly zero output/grads on every
    # backend (see masking.rows_alive) — in-kernel they'd be backend-dependent
    # garbage (uniform over visited tiles vs. uniform over all T columns).
    return zero_dead_rows(out, rows_alive(kv_valid, S, causal=causal,
                                          window=int(window)))
