"""Pallas TPU kernel: split-KV decode attention over a paged KV cache.

The serving cell's decode hot path (DESIGN.md §5): one query row per sequence
against that sequence's pages of the global KV pool.  Reuses PR 3's GQA-native
flash layout — the G grouped query heads of one KV head share their KV tile in
VMEM — but specialized to S = 1 and to *paged* KV:

* **Page-table indirection via scalar prefetch.**  The per-slot page table
  ``(B, P)`` and valid-slot counts ``(B,)`` ride in as scalar-prefetch
  operands (``pltpu.PrefetchScalarGridSpec``), so each KV page's BlockSpec
  index map resolves ``page_table[b, page]`` *before* the kernel body runs and
  the DMA fetches the physical page directly from the pool — no gathered
  contiguous copy of the cache ever exists in HBM.
* **Split-KV grid.**  Grid ``(B, n_splits, pages_per_split)``: the pages
  of one sequence are partitioned into ``n_splits`` independent splits, each
  accumulating an online-softmax partial ``(o, logsumexp)`` per KV head over
  its pages in VMEM scratch.  A grid step fetches a whole page, all KV heads
  in the pool's own layout, so each page is read from HBM once.  Partials
  are combined outside the kernel with the standard logsumexp merge
  (:func:`combine_splits`) — numerically the flash-attention two-level
  reduction.  Splits whose pages all sit beyond the valid count are
  predicated off with ``pl.when`` and drop out of the merge exactly (their
  partial lse is ``NEG_INF``).
* **kv_valid masking for ragged page tails.**  A sequence of length ``n``
  occupies ``ceil(n / page_size)`` pages; columns past ``valid_count[b]`` in
  the last live page are masked with the shared ``masking.NEG_INF`` so padded
  slots never contribute.  The ring invariant (token ``t`` lives at slot
  ``t % C``) makes sliding-window archs need *no extra masking*: a rolling
  pool page holds only attendable tokens once warm.

The jnp reference (:func:`paged_decode_ref`) gathers pages back to the
contiguous layout and runs the same dense softmax as
``models.attention.decode_attention`` — the parity oracle for both this kernel
and the paged model path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.masking import NEG_INF


def round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def gather_pages(pages, table):
    """(N, ps, KV, hd) pool + (B, P) page table -> contiguous (B, P*ps, KV, hd).

    Gathering the table's pages in order reconstructs exactly the contiguous
    ``init_cache`` slot layout (slot s = page s//ps, offset s%ps), which is
    what makes the paged jnp path bit-identical to the contiguous one.
    """
    B, P = table.shape
    g = pages[table]                       # (B, P, ps, KV, hd)
    return g.reshape(B, P * pages.shape[1], *pages.shape[2:])


# ---------------------------------------------------------------------------
# Kernel
# ---------------------------------------------------------------------------

def _decode_kernel(table_ref, vc_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                   acc_ref, m_ref, l_ref, *, ps: int, spp: int, scale: float):
    """One (batch row, split, page) grid step, over every KV head of the page.

    The innermost page loop is sequential, so the running (m, l, acc) online-
    softmax state of each head lives in VMEM scratch across it; at the last
    page of the split the normalized partials and their logsumexps are
    written out.
    """
    b = pl.program_id(0)
    s = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    slot0 = (s * spp + j) * ps        # global slot of this page's first column
    vc = vc_ref[b]

    @pl.when(slot0 < vc)  # pages fully past the valid tail contribute nothing
    def _page():
        for h in range(k_ref.shape[2]):                   # static KV heads
            q = q_ref[0, h].astype(jnp.float32)           # (Gp, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)     # (ps, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
            cols = slot0 + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
            sc = jnp.where(cols < vc, sc, NEG_INF)        # ragged page tail
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
            p = jnp.exp(sc - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot(p, v)
            m_ref[h] = m_new

    @pl.when(j == spp - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_ref[...] + jnp.log(l)


def combine_splits(o_split, lse_split):
    """Merge per-split partials: (B, KV, S, G, hd), (B, KV, S, G) -> (B, KV, G, hd).

    The standard flash-attention logsumexp merge; dead splits carry
    ``lse = NEG_INF`` so their weight underflows to exactly zero.
    """
    m = lse_split.max(axis=2, keepdims=True)
    w = jnp.exp(lse_split - m)                                  # (B, KV, S, G)
    den = jnp.maximum(w.sum(axis=2), 1e-30)                     # (B, KV, G)
    num = (o_split * w[..., None]).sum(axis=2)                  # (B, KV, G, hd)
    return num / den[..., None]


def default_pages_per_split(page_size: int, n_pages_per_seq: int,
                            target_slots: int = 1024) -> int:
    """Pages per split sized so one split covers ~``target_slots`` KV slots
    (one VMEM-resident online-softmax chain); at least 1."""
    return max(1, min(n_pages_per_seq, target_slots // max(page_size, 1)))


def paged_decode_attention(q, k_pages, v_pages, page_table, valid_count, *,
                           pages_per_split: int = 0, interpret=None):
    """Split-KV decode attention over a paged pool.

    q: (B, 1, KV, G, hd); k_pages/v_pages: (N, page_size, KV, hd);
    page_table: (B, P) int32 physical page ids; valid_count: (B,) int32 valid
    slots (<= P * page_size).  Returns (B, 1, KV, G, hd).  Matches
    :func:`paged_decode_ref` (the gathered dense softmax) to flash tolerance.
    ``interpret`` None = derived from the backend
    (:func:`repro.kernels.resolve_interpret`).

    Each grid step fetches one whole page, ``(page_size, KV, hd)``, as the
    pool holds it: its last two dims are the pool's own, so the block obeys
    the TPU tiling rule for any ``KV`` and ``hd``, and the pool is never
    relaid out.  The kernel takes each head with a static index.
    """
    B, S, KV, G, hd = q.shape
    assert S == 1, q.shape
    N, ps = k_pages.shape[0], k_pages.shape[1]
    P = page_table.shape[1]
    spp = pages_per_split or default_pages_per_split(ps, P)
    n_splits = -(-P // spp)
    Pp = n_splits * spp
    if Pp != P:  # pad with trash-page entries; their slots sit past valid_count
        page_table = jnp.pad(page_table, ((0, 0), (0, Pp - P)))
    Gp = round_up(G, 8)                        # 8-sublane query-row tile
    qr = q[:, 0]                               # (B, KV, G, hd)
    if Gp != G:
        qr = jnp.pad(qr, ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    grid = (B, n_splits, spp)
    kernel = functools.partial(_decode_kernel, ps=ps, spp=spp,
                               scale=hd ** -0.5)
    page = pl.BlockSpec((1, ps, KV, hd),
                        lambda b, s, j, pt, vc: (pt[b, s * spp + j], 0, 0, 0))
    o_split, lse_split = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, KV, Gp, hd),
                             lambda b, s, j, pt, vc: (b, 0, 0, 0)),
                page, page,
            ],
            out_specs=[
                pl.BlockSpec((1, KV, 1, Gp, hd),
                             lambda b, s, j, pt, vc: (b, 0, s, 0, 0)),
                pl.BlockSpec((1, KV, 1, Gp, 1),
                             lambda b, s, j, pt, vc: (b, 0, s, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((KV, Gp, hd), jnp.float32),
                pltpu.VMEM((KV, Gp, 1), jnp.float32),
                pltpu.VMEM((KV, Gp, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, n_splits, Gp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, n_splits, Gp, 1), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
        name="paged_decode",
    )(page_table.astype(jnp.int32), valid_count.astype(jnp.int32),
      qr, k_pages, v_pages)

    o = combine_splits(o_split, lse_split[..., 0])[:, :, :G]  # (B, KV, G, hd)
    return o[:, None].astype(q.dtype)


# ---------------------------------------------------------------------------
# jnp reference (parity oracle; identical math to attention.decode_attention)
# ---------------------------------------------------------------------------

def paged_decode_ref(q, k_pages, v_pages, page_table, valid_count):
    """Gather pages to the contiguous layout, then dense masked softmax.

    Bit-identical to ``models.attention.decode_attention(q, gathered_k,
    gathered_v, length=valid_count)`` — the same einsum/softmax sequence on
    the same values — so the paged jnp model path inherits the contiguous
    path's parity guarantees.
    """
    B, _, KV, G, hd = q.shape
    kc = gather_pages(k_pages, page_table)
    vc = gather_pages(v_pages, page_table)
    C = kc.shape[1]
    scale = hd ** -0.5
    s = jnp.einsum("bskgh,btkh->bkgst", q, kc,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(C)[None, :] < jnp.minimum(valid_count, C)[:, None]
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", p, vc)
