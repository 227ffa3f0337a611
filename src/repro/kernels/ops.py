"""jit'd public wrappers around the Pallas kernels, with shape canonicalization
(ragged trailing dims are handled by reshaping to the (L, M, N) canonical layout;
arbitrary-rank stacked parameters reduce over all non-leading axes).

Hyperparameters that vary across steps — ``lr``, ``count`` and the
bias-correction terms derived from it — are *dynamic* operands packed into the
kernels' ``hyper`` vector: a 10-step cosine-schedule run compiles each
(shape, dtype) bucket exactly once (regression-tested in
``tests/test_dispatch.py``).  Only true structure (shapes, interpret mode,
moment betas baked into nothing) stays static.  ``interpret=None`` derives the
mode from the backend (compiled on TPU, interpreted elsewhere —
:func:`repro.kernels.resolve_interpret`).

Under a sharded backend these wrappers are invoked *per shard* from inside the
dispatch layer's ``shard_map`` (``kernels/dispatch.py``): they only ever see
local shapes, so the ``_canon3`` layout and block sizing below adapt to the
shard extents, and nothing here may assume the global array shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import grades_norm as _gn
from repro.kernels import masked_adamw as _ma


def _canon3(x):
    """(L, ...) -> (L, M, N) with N a multiple of 128 where possible."""
    L = x.shape[0]
    rest = int(x.size // L)
    n = 128
    while rest % n != 0 and n > 1:
        n //= 2
    return x.reshape(L, rest // n, n)


def _blocks(shape3, block_m, block_n):
    bm = min(block_m, shape3[1])
    while shape3[1] % bm:
        bm //= 2
    bn = min(block_n, shape3[2])
    while shape3[2] % bn:
        bn //= 2
    return max(bm, 1), max(bn, 1)


@functools.partial(jax.jit, static_argnames=("interpret", "block_m", "block_n"))
def grades_norm(g, prev, frozen=None, *, interpret=None,
                block_m: int = 256, block_n: int = 512):
    """Fused GradES monitor: (norm (L,), new_prev) for stacked (L, ...) grads.

    ``frozen`` ((L,) bool, optional) gates the kernel per layer: frozen rows
    report a zero norm and keep ``prev`` untouched (one flag load instead of
    2 reads + 1 write — freezing is permanent, so their monitor value is dead).
    """
    shape = g.shape
    g3 = _canon3(g)
    bm, bn = _blocks(g3.shape, block_m, block_n)
    norm, new_prev = _gn.grades_norm_kernel(g3, _canon3(prev), frozen,
                                            block_m=bm, block_n=bn,
                                            interpret=interpret)
    return norm, new_prev.reshape(shape)


def _adamw_hyper(lr, count, b1, b2, eps, weight_decay):
    c = jnp.asarray(count, jnp.float32)
    return jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.full((), b1, jnp.float32),
        jnp.full((), b2, jnp.float32),
        jnp.full((), eps, jnp.float32),
        jnp.full((), weight_decay, jnp.float32),
        1.0 - jnp.float32(b1) ** c,
        1.0 - jnp.float32(b2) ** c,
    ])


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "weight_decay",
                                             "interpret"))
def masked_adamw(p, g, m, v, frozen, lr, count, *, b1=0.9, b2=0.95, eps=1e-8,
                 weight_decay=0.0, interpret=None):
    """Frozen-gated AdamW on a stacked (L, ...) leaf.  ``lr`` and ``count``
    are dynamic (no recompile under a schedule)."""
    shape = p.shape
    c3 = _canon3
    p3 = c3(p)
    bm, bn = _blocks(p3.shape, 256, 512)
    hyper = _adamw_hyper(lr, count, b1, b2, eps, weight_decay)
    outs = _ma.masked_adamw_kernel(
        p3, c3(g), c3(m), c3(v), frozen, hyper, block_m=bm, block_n=bn,
        interpret=interpret)
    return tuple(o.reshape(shape) for o in outs)


@functools.partial(jax.jit, static_argnames=("b1", "weight_decay", "interpret"))
def masked_sgd(p, g, m, frozen, lr, *, b1=0.9, weight_decay=0.0,
               interpret=None):
    """Frozen-gated SGD-momentum on a stacked (L, ...) leaf (dynamic ``lr``)."""
    shape = p.shape
    c3 = _canon3
    p3 = c3(p)
    bm, bn = _blocks(p3.shape, 256, 512)
    hyper = _adamw_hyper(lr, 1, b1, 0.0, 0.0, weight_decay)
    p3, m3 = _ma.masked_sgd_kernel(p3, c3(g), c3(m), frozen, hyper,
                                   block_m=bm, block_n=bn, interpret=interpret)
    return p3.reshape(shape), m3.reshape(shape)
