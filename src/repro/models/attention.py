"""Attention: GQA with RoPE — Pallas flash / full / blockwise / decode paths.

Layout conventions
  q        : (B, S, KV, G, hd)   G = n_heads // n_kv_heads (grouped query heads)
  k        : (B, T, KV, hd)
  v        : (B, T, KV, hdv)     hdv = hd but for latent attention (MLA)
  output   : (B, S, KV, G, hdv)  scores scaled by hd ** -0.5

``attention()`` is the production entry point: it routes through the kernel
backend machinery (``kernels/dispatch.py``, same ``"pallas" | "jnp" | "auto"``
semantics as the GradES hot path).  On the pallas backend the call runs the
fused flash fwd+bwd kernel pair (``kernels/flash_attention.py`` — custom_vjp,
GQA-native, window/kv_valid masking, shard_map-wrapped under a mesh); shapes
the kernel can't take fall back per call to the jnp paths below, selected by
``chunk_threshold`` exactly as before.

The blockwise path is an online-softmax (flash-attention) formulation in pure
JAX: a ``lax.scan`` over query chunks with an inner ``fori_loop`` over KV
chunks carrying (running max, running denominator, accumulator).  It bounds
the score tensor at (q_chunk × kv_chunk) regardless of sequence length, which
is what makes the 32k/500k shape cells lowerable, and it doubles as the
fallback/reference schedule for the Pallas kernel (identical masking via the
shared ``kernels.masking.NEG_INF``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as _dispatch
from repro.kernels.masking import (NEG_INF, band_live, rows_alive,
                                   zero_dead_rows)


def _mask_bias(q_pos, k_pos, *, causal: bool, window: int):
    """(..., S, T) additive bias from positions."""
    ok = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        ok &= k_pos[..., None, :] <= q_pos[..., :, None]
    if window:
        ok &= k_pos[..., None, :] > q_pos[..., :, None] - window
    return jnp.where(ok, 0.0, NEG_INF)


def full_attention(q, k, v, *, causal: bool = True, window: int = 0,
                   q_offset=0, kv_valid: Optional[jax.Array] = None):
    """Materializes the (S, T) score matrix — use for S·T small enough."""
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    scale = hd ** -0.5
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = q_offset + jnp.arange(S)
    k_pos = jnp.arange(T)
    bias = _mask_bias(q_pos, k_pos, causal=causal, window=window)
    scores = scores + bias
    if kv_valid is not None:  # (B, T) mask for padded cache slots
        scores = jnp.where(kv_valid[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    # fully-masked rows: exactly zero on every backend (masking.rows_alive)
    return zero_dead_rows(out, rows_alive(kv_valid, S, causal=causal,
                                          window=window, offset=q_offset))


def blockwise_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        kv_valid: Optional[jax.Array] = None,
                        q_chunk: int = 1024, kv_chunk: int = 1024):
    """Flash-style online-softmax attention; O(q_chunk·kv_chunk) score memory."""
    B, S, KV, G, hd = q.shape
    T = k.shape[1]
    q_chunk = min(q_chunk, S)
    kv_chunk = min(kv_chunk, T)
    assert S % q_chunk == 0 and T % kv_chunk == 0
    nq, nkv = S // q_chunk, T // kv_chunk
    scale = hd ** -0.5

    qs = q.reshape(B, nq, q_chunk, KV, G, hd)
    ks = k.reshape(B, nkv, kv_chunk, KV, hd)
    hdv = v.shape[-1]
    vs = v.reshape(B, nkv, kv_chunk, KV, hdv)
    valid = (None if kv_valid is None
             else kv_valid.reshape(B, nkv, kv_chunk))

    def q_block(carry, inp):
        qi, qb = inp  # index, (B, qc, KV, G, hd)
        m0 = jnp.full((B, KV, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, q_chunk, KV, G, hdv), jnp.float32)

        def live_block(ki, state):
            m, l, acc = state
            kb = jax.lax.dynamic_index_in_dim(ks, ki, 1, keepdims=False)
            vb = jax.lax.dynamic_index_in_dim(vs, ki, 1, keepdims=False)
            s = jnp.einsum("bskgh,btkh->bkgst", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            q_pos = qi * q_chunk + jnp.arange(q_chunk)
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = s + _mask_bias(q_pos, k_pos, causal=causal, window=window)
            if valid is not None:
                vb_mask = jax.lax.dynamic_index_in_dim(valid, ki, 1,
                                                       keepdims=False)
                s = jnp.where(vb_mask[:, None, None, None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc = acc * corr.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
                "bkgst,btkh->bskgh", p, vb, preferred_element_type=jnp.float32)
            return m_new, l_new, acc

        def kv_block(ki, state):
            # Static trip count (0, nkv) keeps the loop reverse-differentiable
            # (this path is the *training* fallback for shapes the flash
            # kernel can't take; a dynamic-in-qi bound breaks jax.grad), and
            # the lax.cond skips KV blocks fully outside the causal/window
            # band — same FLOPs as the old dynamic bounds, same band
            # definition as the Pallas kernels (masking.band_live).
            live = band_live(qi * q_chunk, q_chunk, ki * kv_chunk, kv_chunk,
                             causal=causal, window=window)
            if live is True:
                return live_block(ki, state)
            return jax.lax.cond(live, lambda st: live_block(ki, st),
                                lambda st: st, state)

        m, l, acc = jax.lax.fori_loop(0, nkv, kv_block, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30).transpose(0, 3, 1, 2)[..., None]
        return carry, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_block, None, (jnp.arange(nq), qs.transpose(1, 0, 2, 3, 4, 5)))
    out = blocks.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, KV, G, hdv)
    # fully-masked rows: exactly zero on every backend (masking.rows_alive)
    return zero_dead_rows(out, rows_alive(kv_valid, S, causal=causal,
                                          window=window))


def decode_attention(q, k_cache, v_cache, *, length, window: int = 0):
    """Single-position query against a (possibly rolling) cache.

    q: (B, 1, KV, G, hd); caches: (B, C, KV, hd) where C = max_len or window.
    ``length`` (B,)-broadcastable count of valid tokens written so far.
    """
    B, _, KV, G, hd = q.shape
    C = k_cache.shape[1]
    scale = hd ** -0.5
    s = jnp.einsum("bskgh,btkh->bkgst", q, k_cache,
                   preferred_element_type=jnp.float32) * scale
    slot = jnp.arange(C)
    length = jnp.asarray(length).reshape(-1)
    valid = slot[None, :] < jnp.minimum(length, C)[:, None]       # (B, C)
    if window:
        # rolling buffer: all C=window slots valid once warm; handled by the min().
        pass
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", p, v_cache)


def _divisor_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (ragged lengths, e.g. 1500 frames)."""
    c = min(n, target)
    while n % c:
        c -= 1
    return c


def attention(q, k, v, *, causal=True, window=0,
              kv_valid: Optional[jax.Array] = None, backend=None,
              chunk_threshold: int = 8192, q_chunk: int = 1024,
              kv_chunk: int = 1024):
    """Backend-routed attention (the production entry point).

    ``backend`` is a resolved :class:`~repro.kernels.dispatch.KernelBackend`,
    a ``"pallas" | "jnp" | "auto"`` string, or None (= auto: flash on TPU, jnp
    elsewhere) — model configs thread it here via ``ModelConfig.attn_backend``
    / ``TrainConfig.kernels``.  On the pallas backend the fused flash fwd+bwd
    kernels run (shard_map-wrapped under a multi-device mesh); calls the
    kernel can't take (see ``dispatch.flash_attention_restriction``) fall back
    per call — warning once when pallas was forced — to the jnp paths:
    full attention for short sequences, blockwise beyond ``chunk_threshold``.
    """
    backend = _dispatch.normalize_backend(backend)
    if _dispatch.flash_ok(q, k, backend):
        return _dispatch.fused_flash_attention(
            q, k, v, causal=causal, window=window, kv_valid=kv_valid,
            backend=backend)
    S, T = q.shape[1], k.shape[1]
    if max(S, T) > chunk_threshold:
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   kv_valid=kv_valid,
                                   q_chunk=_divisor_chunk(S, q_chunk),
                                   kv_chunk=_divisor_chunk(T, kv_chunk))
    return full_attention(q, k, v, causal=causal, window=window,
                          kv_valid=kv_valid)
