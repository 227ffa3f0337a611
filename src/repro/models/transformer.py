"""Decoder LM stack for the dense / moe / hybrid families.

Layers are *stacked* (leading L axis) and iterated with ``jax.lax.scan`` so the HLO
stays compact for 40–62-layer configs (one while-loop, not L inlined blocks); this is
also what makes GradES's per-(layer, type) freeze masks representable as (L,) boolean
vectors (see repro/core/grades.py).

Tier 1.5 (DESIGN.md §2): when a :class:`~repro.core.partition.SegmentPlan` is
passed, the single scan is replaced by a chain of **segment scans** — each
segment slices its ``[lo, hi)`` rows of the stacked params (static bounds) and
applies ``stop_gradient`` to exactly its signature's matrix types, so the
backward pass never builds those segments' dW einsums and per-layer freezes
shrink FLOPs without waiting for a whole type to converge.  Forward values and
the surviving gradients are bit-identical to the monolithic scan (same per-layer
op sequence; slicing only re-groups the loop).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.distributed.sharding import logical_constraint
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.common import (apply_rope, attn_call_args, cross_entropy,
                                 init_dense, rms_norm, shard_batch)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_layer_params(key, cfg: ModelConfig, n_layers: int, dtype: str,
                      *, dense: bool = False) -> Dict[str, Any]:
    """One stack of ``n_layers`` layers; ``dense`` makes the leading dense
    layers of a mixture-of-experts model (a SwiGLU of ``cfg.dense_d_ff``)."""
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = iter(jax.random.split(key, 16))
    L = n_layers
    p: Dict[str, Any] = {"attn_norm": jnp.zeros((L, d), jnp.dtype(dtype)),
                         "wq": init_dense(next(ks), (L, d, qd), dtype=dtype)}
    if cfg.mla:
        r, H = cfg.kv_lora_rank, cfg.n_heads
        p.update({
            "wkv_a": init_dense(next(ks), (L, d, r + cfg.qk_rope_head_dim),
                                dtype=dtype),
            "kv_norm": jnp.zeros((L, r), jnp.dtype(dtype)),
            "wkv_b": init_dense(next(ks), (L, r, H * (cfg.qk_nope_head_dim
                                                      + cfg.v_head_dim)),
                                dtype=dtype),
        })
    else:
        p.update({"wk": init_dense(next(ks), (L, d, kvd), dtype=dtype),
                  "wv": init_dense(next(ks), (L, d, kvd), dtype=dtype)})
    p.update({
        "wo": init_dense(next(ks), (L, cfg.attn_out_dim, d), dtype=dtype),
        "mlp_norm": jnp.zeros((L, d), jnp.dtype(dtype)),
    })
    if cfg.moe is not None and not dense:
        e, f = cfg.moe.held, cfg.moe.d_ff
        p.update({
            "router": init_dense(next(ks), (L, d, cfg.moe.n_experts),
                                 dtype=dtype),
            "w_gate": init_dense(next(ks), (L, e, d, f), dtype=dtype),
            "w_up": init_dense(next(ks), (L, e, d, f), dtype=dtype),
            "w_down": init_dense(next(ks), (L, e, f, d), in_axis=-2, dtype=dtype),
        })
        if cfg.moe.scoring == "sigmoid":
            # the correction bias: small and nonzero, so that weighting by
            # the biased score would show; nothing ever trains it
            p["router_bias"] = 0.05 * jax.random.normal(
                next(ks), (L, cfg.moe.n_experts), jnp.dtype(dtype))
        if cfg.moe.shared_d_ff:
            fs = cfg.moe.shared_d_ff
            p.update({
                "shared_gate": init_dense(next(ks), (L, d, fs), dtype=dtype),
                "shared_up": init_dense(next(ks), (L, d, fs), dtype=dtype),
                "shared_down": init_dense(next(ks), (L, fs, d), dtype=dtype),
            })
    elif cfg.mlp_act == "swiglu":
        f = cfg.dense_d_ff if dense else cfg.d_ff
        p.update({
            "w_gate": init_dense(next(ks), (L, d, f), dtype=dtype),
            "w_up": init_dense(next(ks), (L, d, f), dtype=dtype),
            "w_down": init_dense(next(ks), (L, f, d), dtype=dtype),
        })
    else:  # gelu
        p.update({
            "w_up": init_dense(next(ks), (L, d, cfg.d_ff), dtype=dtype),
            "w_down": init_dense(next(ks), (L, cfg.d_ff, d), dtype=dtype),
        })
    if cfg.ssm is not None:
        p.update(ssm_lib.init_ssm_params(next(ks), cfg, L, dtype))
    return p


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = cfg.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    params = {
        "embed": init_dense(k1, (cfg.vocab, cfg.d_model), in_axis=-1, dtype=dtype),
        "layers": init_layer_params(k2, cfg, cfg.n_layers, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.dtype(dtype)),
    }
    if cfg.n_dense_layers:
        params["dense_layers"] = init_layer_params(
            k4, cfg, cfg.n_dense_layers, dtype, dense=True)
    if not cfg.tie_embeddings:
        params["lm_head"] = init_dense(k3, (cfg.d_model, cfg.vocab), dtype=dtype)
    return params


# logical axes for every parameter (drives both pjit shardings and constraints).
# Attention projections are tensor-parallel ONLY when both head counts divide the
# model axis: sharding the fused q/kv dim when heads don't divide makes XLA
# re-gather the per-head layout every layer (decode: the whole KV cache) — worse
# than replicating the projections.  ``model_size=None`` (tests, single device)
# keeps the TP axes.
def layer_param_axes(cfg: ModelConfig, model_size: Optional[int] = None,
                     *, dense: bool = False) -> Dict[str, Tuple]:
    tp_attn = model_size is None or (cfg.n_heads % model_size == 0
                                     and cfg.n_kv_heads % model_size == 0)
    qax = "qdim" if tp_attn else None
    kvax = "kvdim" if tp_attn else None
    ax: Dict[str, Tuple] = {
        "attn_norm": (None, None),
        "wq": (None, "fsdp", qax),
        "wo": (None, qax, "fsdp"),
        "mlp_norm": (None, None),
    }
    if cfg.mla:  # the latent is shared by every head: not tensor-parallel
        ax.update({"wkv_a": (None, "fsdp", None), "kv_norm": (None, None),
                   "wkv_b": (None, None, qax)})
    else:
        ax.update({"wk": (None, "fsdp", kvax), "wv": (None, "fsdp", kvax)})
    if cfg.moe is not None and not dense:
        ax.update({
            "router": (None, "fsdp", None),
            "w_gate": (None, "expert", "fsdp", None),
            "w_up": (None, "expert", "fsdp", None),
            "w_down": (None, "expert", None, "fsdp"),
        })
        if cfg.moe.scoring == "sigmoid":
            ax["router_bias"] = (None, None)
        if cfg.moe.shared_d_ff:
            ax.update({"shared_gate": (None, "fsdp", "ffn"),
                       "shared_up": (None, "fsdp", "ffn"),
                       "shared_down": (None, "ffn", "fsdp")})
    else:
        ax.update({
            "w_gate": (None, "fsdp", "ffn"),
            "w_up": (None, "fsdp", "ffn"),
            "w_down": (None, "ffn", "fsdp"),
        })
        if cfg.mlp_act != "swiglu":
            ax.pop("w_gate")
    if cfg.ssm is not None:
        ax.update({
            "ssm_in": (None, "fsdp", "ssm_inner"),
            "ssm_conv": (None, None, "ssm_inner"),
            "ssm_x": (None, "ssm_inner", None),
            "ssm_dt": (None, None, "ssm_inner"),
            "ssm_a_log": (None, "ssm_inner", None),
            "ssm_skip": (None, "ssm_inner"),
            "ssm_out": (None, "ssm_inner", "fsdp"),
        })
    return ax


def param_logical_axes(cfg: ModelConfig, model_size: Optional[int] = None) -> Dict[str, Any]:
    out = {
        "embed": ("vocab", "fsdp"),
        "layers": layer_param_axes(cfg, model_size),
        "final_norm": (None,),
    }
    if cfg.n_dense_layers:
        out["dense_layers"] = layer_param_axes(cfg, model_size, dense=True)
    if not cfg.tie_embeddings:
        out["lm_head"] = ("fsdp", "vocab")
    return out


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _qkv(x, lp, cfg: ModelConfig, positions):
    if cfg.mla:
        return _mla_qkv(x, lp, cfg, positions)
    B, S, _ = x.shape
    hd, KV = cfg.resolved_head_dim, cfg.n_kv_heads
    G = cfg.n_heads // KV
    q = (x @ lp["wq"]).reshape(B, S, KV, G, hd)
    k = (x @ lp["wk"]).reshape(B, S, KV, hd)
    v = (x @ lp["wv"]).reshape(B, S, KV, hd)
    q = apply_rope(q.reshape(B, S, KV * G, hd), positions, cfg.rope_theta
                   ).reshape(B, S, KV, G, hd)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _mla_qkv(x, lp, cfg: ModelConfig, positions):
    """Latent attention's projections (DeepSeek-V2/V3, no q LoRA): q = x·W_q
    in heads of nope + rope dims; ``[c_kv, k_pe] = x·W_kv_a``, RMSNorm on the
    latent ``c_kv``, ``[k_nope, v] = c_kv·W_kv_b``; ``k_pe`` is one rope head
    shared by all heads.  Keys are materialised as ``[k_nope, k_pe]`` so the
    call goes through ``attention()`` with KV = heads, G = 1."""
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    r = cfg.kv_lora_rank
    q = (x @ lp["wq"]).reshape(B, S, H, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn], apply_rope(q[..., dn:], positions, cfg.rope_theta)], -1)
    kv_a = x @ lp["wkv_a"]
    c_kv = rms_norm(kv_a[..., :r], lp["kv_norm"], cfg.norm_eps)
    k_pe = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta)
    kv = (c_kv @ lp["wkv_b"]).reshape(B, S, H, dn + cfg.v_head_dim)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (B, S, H, dr))],
                        -1)
    return q.reshape(B, S, H, 1, dn + dr), k, kv[..., dn:]


def attn_block(x, lp, cfg: ModelConfig, positions, *, attn_args: Dict[str, Any]):
    """Pre-norm attention residual branch; returns (delta, (k, v)) for caching.

    When ``cfg.seq_parallel_attn`` (heads don't divide the TP axis), the block
    runs sequence-parallel: activations are sharded on the SEQ dim over "model"
    so the O(S·T) score tensor and the attention FLOPs partition across the TP
    axis instead of being replicated; GSPMD inserts the k/v all-gather and the
    seq<->model transitions around the block (Megatron-SP adapted to GSPMD).
    """
    B, S = x.shape[:2]
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    sp = cfg.seq_parallel_attn and S > 1
    if sp:
        h = logical_constraint(h, ("batch", "attn_seq", None))
    q, k, v = _qkv(h, lp, cfg, positions)
    if sp:
        q = logical_constraint(q, ("batch", "attn_seq", None, None, None))
    args = attn_call_args(cfg, attn_args)
    if sp:
        # sequence-sharded activations can't be shard_mapped per (batch, KV
        # head) — a shard would need its neighbours' KV.  Keep the jnp
        # formulation; GSPMD partitions it via the constraints above.
        args["backend"] = "jnp"
    o = attn_lib.attention(q, k, v, causal=True, window=cfg.swa_window, **args)
    if sp:
        o = logical_constraint(o, ("batch", "attn_seq", None, None, None))
    o = o.reshape(B, S, cfg.attn_out_dim) @ lp["wo"]
    return o, (k, v)


def mlp_block(x, lp, cfg: ModelConfig):
    """Pre-norm FFN/MoE residual branch; returns (delta, aux_loss, load):
    ``load`` (held experts,) is the tokens x picks routed to each expert the
    layer holds, None where the layer has no held-expert routing."""
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if "router" in lp:
        if cfg.moe.scoring == "sigmoid":
            out, load = moe_lib.held_expert_block(h, lp, cfg.moe)
            return out, jnp.float32(0), load
        return moe_lib.moe_block(h, lp, cfg.moe) + (None,)
    if cfg.mlp_act == "swiglu":
        from repro.models.mlp import swiglu
        return (swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]),
                jnp.float32(0), None)
    from repro.models.mlp import gelu_mlp
    return gelu_mlp(h, lp["w_up"], lp["w_down"]), jnp.float32(0), None


def decoder_block(x, lp, cfg: ModelConfig, positions, *, ssm_state=None,
                  attn_args: Dict[str, Any]):
    a_out, kv = attn_block(x, lp, cfg, positions, attn_args=attn_args)
    new_ssm = None
    if cfg.ssm is not None:  # hymba: attention and mamba heads in parallel
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        m_out, new_ssm = ssm_lib.mamba_head(h, lp, cfg, state=ssm_state)
        a_out = (a_out + m_out) * 0.5
    x = x + a_out
    m, aux, load = mlp_block(x, lp, cfg)
    x = shard_batch(x + m)
    return x, kv, new_ssm, aux, load


def _compute_dtype(lp, cfg: ModelConfig):
    """A layer's params as the body computes with them: floats in
    ``cfg.dtype``, but a sigmoid-scored router's in float32."""
    keep = (moe_lib.SIGMOID_FLOAT32_LEAVES if cfg.moe is not None
            and cfg.moe.scoring == "sigmoid" else ())
    return {k: (a.astype(cfg.dtype) if k not in keep
                and jnp.issubdtype(a.dtype, jnp.floating) else a)
            for k, a in lp.items()}


# ---------------------------------------------------------------------------
# Forward (training / prefill) via scan over stacked layers
# ---------------------------------------------------------------------------

def scan_layers(body, x, layers, plan=None):
    """Run ``body`` over the stacked layer params — one ``lax.scan``, or the
    plan's chain of segment scans (Tier 1.5, DESIGN.md §2).

    Each segment takes a static ``[lo, hi)`` slice of every stacked leaf and
    wraps its signature's types in ``stop_gradient`` *outside* the scan, so
    JAX's partial evaluation treats them as constants and the backward scan
    for the segment contains no dW computation for them at all.  Per-segment
    ys are concatenated back to the full ``(L, ...)`` stacks, keeping the
    collected KV-cache layout identical to the monolithic scan.
    """
    if plan is None or plan.trivial:
        return jax.lax.scan(body, x, layers)
    ys_parts = []
    for lo, hi, sig in plan.segments:
        seg = jax.tree.map(
            lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=0), layers)
        if sig:
            seg = {k: (jax.tree.map(jax.lax.stop_gradient, sub) if k in sig
                       else sub) for k, sub in seg.items()}
        x, ys = jax.lax.scan(body, x, seg)
        ys_parts.append(ys)
    if len(ys_parts) == 1:
        return x, ys_parts[0]
    return x, jax.tree.map(lambda *p: jnp.concatenate(p, axis=0), *ys_parts)


def forward(params, cfg: ModelConfig, tokens, *, remat: str = "none",
            collect_cache: bool = False, cache_window: int = 0,
            attn_args: Optional[Dict[str, Any]] = None, plan=None):
    """tokens: (B, S) int32 -> (logits, aux, ys).

    ``ys`` holds the scan's per-layer outputs: with ``collect_cache`` the
    KV/SSM state for decode; in a held-expert layer ``expert_load``, the
    ``(n_layers, held experts)`` tokens x picks routed to each held expert.
    ``plan`` (a :class:`~repro.core.partition.SegmentPlan`, static per jit)
    segments the layer scan for per-layer backward-FLOP elimination.
    """
    attn_args = attn_args or {}
    B, S = tokens.shape
    x = shard_batch(params["embed"].astype(cfg.dtype)[tokens])
    positions = jnp.arange(S)[None, :]

    init_ssm = None
    if cfg.ssm is not None:
        di = cfg.ssm.expand * cfg.d_model
        init_ssm = (jnp.zeros((B, di, cfg.ssm.state_dim), jnp.float32),
                    jnp.zeros((B, cfg.ssm.conv_width - 1, di), cfg.dtype))

    def body(x, lp):
        lp = _compute_dtype(lp, cfg)
        x, kv, new_ssm, aux, load = decoder_block(
            x, lp, cfg, positions, ssm_state=init_ssm, attn_args=attn_args)
        ys = {"aux": aux}
        if load is not None:
            ys["expert_load"] = load
        if collect_cache:
            k, v = kv
            if cache_window and cache_window < S:
                k, v = k[:, -cache_window:], v[:, -cache_window:]
            ys["k"], ys["v"] = k, v
            if new_ssm is not None:
                ys["ssm_h"], ys["ssm_conv"] = new_ssm
        return x, ys

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_no_batch_dims)

    if "dense_layers" in params:
        if collect_cache:
            raise NotImplementedError("no decode cache for leading dense layers")
        # the leading dense layers: the same block and remat, before the
        # stacked scan; the segment plan covers the scan's layers only
        x, _ = scan_layers(body, x, params["dense_layers"])
    x, ys = scan_layers(body, x, params["layers"], plan)
    x = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(cfg.dtype)
    logits = x @ head
    logits = logical_constraint(logits, ("batch", None, "vocab"))
    aux = ys.pop("aux").mean()
    return logits, aux, ys


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def cache_len(cfg: ModelConfig, max_len: int) -> int:
    return min(cfg.swa_window, max_len) if cfg.swa_window else max_len


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int):
    C = cache_len(cfg, max_len)
    L, hd, KV = cfg.n_layers, cfg.resolved_head_dim, cfg.n_kv_heads
    cache = {
        "k": jnp.zeros((L, batch, C, KV, hd), cfg.dtype),
        "v": jnp.zeros((L, batch, C, KV, hd), cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.ssm is not None:
        di = cfg.ssm.expand * cfg.d_model
        cache["ssm_h"] = jnp.zeros((L, batch, di, cfg.ssm.state_dim), jnp.float32)
        cache["ssm_conv"] = jnp.zeros((L, batch, cfg.ssm.conv_width - 1, di), cfg.dtype)
    return cache


def prefill(params, cfg: ModelConfig, tokens, max_len: int,
            attn_args: Optional[Dict[str, Any]] = None, plan=None):
    """Full-sequence forward that also builds the decode cache."""
    B, S = tokens.shape
    C = cache_len(cfg, max_len)
    logits, aux, ys = forward(params, cfg, tokens, collect_cache=True,
                              cache_window=C if cfg.swa_window else 0,
                              attn_args=attn_args, plan=plan)
    k, v = ys["k"], ys["v"]  # (L, B, min(S,C), KV, hd)
    if k.shape[2] < C:
        zeros = jnp.zeros(k.shape[:2] + (C - k.shape[2],) + k.shape[3:], k.dtype)
        k = jnp.concatenate([k, zeros], axis=2)
        v = jnp.concatenate([v, zeros], axis=2)
    elif cfg.swa_window and S > C:
        # ring invariant: token j lives at slot j % C.  The collected window holds
        # tokens S-C..S-1 at slots 0..C-1; rotate so decode_step's (pos % C) write
        # evicts the oldest token.
        k = jnp.roll(k, S % C, axis=2)
        v = jnp.roll(v, S % C, axis=2)
    cache = {"k": k, "v": v, "pos": jnp.int32(S)}
    if cfg.ssm is not None:
        cache["ssm_h"], cache["ssm_conv"] = ys["ssm_h"], ys["ssm_conv"]
    return logits, cache


def decode_step(params, cfg: ModelConfig, cache, tokens):
    """tokens: (B, 1). One decode step; returns (logits, new cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    x = params["embed"].astype(cfg.dtype)[tokens]              # (B, 1, D)
    positions = jnp.full((B, 1), pos, jnp.int32)
    C = cache["k"].shape[2]
    slot = pos % C if cfg.swa_window else jnp.minimum(pos, C - 1)

    xs = {"lp": params["layers"], "k": cache["k"], "v": cache["v"]}
    if cfg.ssm is not None:
        xs["ssm_h"], xs["ssm_conv"] = cache["ssm_h"], cache["ssm_conv"]

    def body(x, layer_in):
        lp = _compute_dtype(layer_in["lp"], cfg)
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = _qkv(h, lp, cfg, positions)
        kc = jax.lax.dynamic_update_slice_in_dim(layer_in["k"], k_new, slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(layer_in["v"], v_new, slot, axis=1)
        o = attn_lib.decode_attention(q, kc, vc, length=pos + 1,
                                      window=cfg.swa_window)
        a_out = o.reshape(B, 1, cfg.q_dim) @ lp["wo"]
        ys = {"k": kc, "v": vc}
        if cfg.ssm is not None:
            m_out, (h2, conv2) = ssm_lib.mamba_head(
                h, lp, cfg, state=(layer_in["ssm_h"], layer_in["ssm_conv"]))
            a_out = (a_out + m_out) * 0.5
            ys["ssm_h"], ys["ssm_conv"] = h2, conv2
        x = x + a_out
        m = mlp_block(x, lp, cfg)[0]
        return x + m, ys

    x, ys = jax.lax.scan(body, x, xs)
    x = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(cfg.dtype)
    logits = x @ head
    new_cache = {"k": ys["k"], "v": ys["v"], "pos": pos + 1}
    if cfg.ssm is not None:
        new_cache["ssm_h"], new_cache["ssm_conv"] = ys["ssm_h"], ys["ssm_conv"]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Serving: paged KV pool (DESIGN.md §5)
# ---------------------------------------------------------------------------

def paged_cache_len(cfg: ModelConfig, max_len: int, page_size: int) -> int:
    """Per-slot logical cache extent, rounded up to whole pages.

    For SWA archs this must be the window itself (the ring invariant
    ``slot = pos % C`` only matches the contiguous path when C == window), so
    ``page_size`` must divide the window; causal caches just round up and the
    per-slot valid count masks the padded tail slots.
    """
    C = cache_len(cfg, max_len)
    if cfg.swa_window and C == cfg.swa_window and C % page_size:
        raise ValueError(
            f"page_size {page_size} must divide the sliding window {C} "
            f"(ring slot = pos % C needs whole pages)")
    return -(-C // page_size) * page_size


def init_paged_pool(cfg: ModelConfig, max_slots: int, max_len: int,
                    page_size: int, n_pages: int = 0):
    """Device state for the paged serving cell: a global page pool shared by
    all decode slots plus per-slot page tables and lengths.

    Page 0 is the *trash page*: free slots' table rows point at it, so their
    (masked, discarded) decode writes never touch a live sequence's pages.
    The default pool size budgets every slot full plus the trash page;
    callers may oversubscribe/undersubscribe via ``n_pages``.
    """
    C = paged_cache_len(cfg, max_len, page_size)
    pps = C // page_size
    n_pages = n_pages or (1 + max_slots * pps)
    L, hd, KV = cfg.n_layers, cfg.resolved_head_dim, cfg.n_kv_heads
    pool = {
        "k_pages": jnp.zeros((L, n_pages, page_size, KV, hd), cfg.dtype),
        "v_pages": jnp.zeros((L, n_pages, page_size, KV, hd), cfg.dtype),
        "page_table": jnp.zeros((max_slots, pps), jnp.int32),
        "lengths": jnp.zeros((max_slots,), jnp.int32),
    }
    if cfg.ssm is not None:
        di = cfg.ssm.expand * cfg.d_model
        pool["ssm_h"] = jnp.zeros((L, max_slots, di, cfg.ssm.state_dim),
                                  jnp.float32)
        pool["ssm_conv"] = jnp.zeros((L, max_slots, cfg.ssm.conv_width - 1, di),
                                     cfg.dtype)
    return pool


def write_prefill_pages(pool, row_of_slot, table_rows, ys, lengths):
    """Scatter a *batch* of prefilled sequences into their allocated pages.

    ``ys`` is the ``collect_cache`` tree from :func:`forward` over a (B, S)
    prompt batch; row ``i`` carries a true prompt of ``lengths[i]`` tokens
    (rows may be padding — give them ``lengths[i] == 0`` and a zero
    ``table_rows[i]`` and every write they make lands on the trash page).
    ``row_of_slot`` maps each pool slot to its batch row (−1 = slot
    untouched), so one call admits a whole prefill group with fixed shapes —
    one jit entry per prompt length regardless of group size.

    Token ``t`` lands at ring slot ``t % C``: for causal prompts (S <= C)
    that is the contiguous layout; for SWA prompts longer than the window it
    reproduces exactly the rolled ring the contiguous :func:`prefill` builds.
    """
    k, v = ys["k"], ys["v"]                          # (L, B, S, KV, hd)
    S = k.shape[2]
    ps = pool["k_pages"].shape[2]
    C = table_rows.shape[1] * ps
    t = jnp.arange(S)
    live = (t[None, :] < lengths[:, None]) & (t[None, :] >= lengths[:, None] - C)
    slotpos = t % C
    phys = jnp.where(live, table_rows[:, slotpos // ps], 0)      # (B, S)
    off = slotpos % ps
    sel = row_of_slot >= 0
    safe = jnp.maximum(row_of_slot, 0)
    pool = dict(pool)
    pool["k_pages"] = pool["k_pages"].at[:, phys, off].set(k)
    pool["v_pages"] = pool["v_pages"].at[:, phys, off].set(v)
    pool["page_table"] = jnp.where(sel[:, None], table_rows[safe],
                                   pool["page_table"])
    pool["lengths"] = jnp.where(sel, lengths[safe], pool["lengths"])
    if "ssm_h" in pool:
        pool["ssm_h"] = jnp.where(sel[None, :, None, None],
                                  ys["ssm_h"][:, safe], pool["ssm_h"])
        pool["ssm_conv"] = jnp.where(sel[None, :, None, None],
                                     ys["ssm_conv"][:, safe], pool["ssm_conv"])
    return pool


def reset_slots(pool, mask):
    """Point freed slots (``mask`` (B,) bool) back at the trash page so their
    idle decode writes can never corrupt pages reallocated to new sequences."""
    pool = dict(pool)
    pool["page_table"] = jnp.where(mask[:, None], 0, pool["page_table"])
    pool["lengths"] = jnp.where(mask, 0, pool["lengths"])
    return pool


def decode_step_paged(params, cfg: ModelConfig, pool, tokens, *, active=None,
                      attn_args: Optional[Dict[str, Any]] = None):
    """tokens: (B, 1) over the B decode slots.  One paged decode step.

    The paged counterpart of :func:`decode_step` with *per-slot* positions
    (``pool["lengths"]``), so sequences at different depths decode in one
    batch — the continuous-batching substrate.  Writes land at ring slot
    ``lengths % C`` (SWA) / ``min(lengths, C-1)`` (causal) through the page
    table; attention runs either through the Pallas split-KV kernel
    (``kernels/decode_attention.py``, routed via ``dispatch.paged_decode_ok``)
    or the jnp gather path, which is bit-identical to the contiguous
    :func:`decode_step` at equal positions.  ``active`` (B,) gates the length
    increment; inactive slots write to the trash page and their outputs are
    host-discarded.
    """
    from repro.kernels import dispatch as _dispatch
    args = attn_call_args(cfg, attn_args)
    backend = _dispatch.normalize_backend(args.get("backend"))
    B = tokens.shape[0]
    lengths = pool["lengths"]
    x = params["embed"].astype(cfg.dtype)[tokens]              # (B, 1, D)
    positions = lengths[:, None]
    table = pool["page_table"]
    P, ps = table.shape[1], pool["k_pages"].shape[2]
    C = P * ps
    slot = lengths % C if cfg.swa_window else jnp.minimum(lengths, C - 1)
    if active is None:
        active = jnp.ones((B,), bool)
    phys = jnp.take_along_axis(table, (slot // ps)[:, None], axis=1)[:, 0]
    # inactive slots scatter to the trash page: a retired slot's pages can be
    # handed to a new request without an intervening reset dispatch
    phys = jnp.where(active, phys, 0)
    off = slot % ps
    vcount = jnp.minimum(lengths + 1, C)

    xs = {"lp": params["layers"], "k": pool["k_pages"], "v": pool["v_pages"]}
    if cfg.ssm is not None:
        xs["ssm_h"], xs["ssm_conv"] = pool["ssm_h"], pool["ssm_conv"]

    def body(x, layer_in):
        lp = _compute_dtype(layer_in["lp"], cfg)
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        q, k_new, v_new = _qkv(h, lp, cfg, positions)
        kp = layer_in["k"].at[phys, off].set(k_new[:, 0])
        vp = layer_in["v"].at[phys, off].set(v_new[:, 0])
        if _dispatch.paged_decode_ok(q, kp, backend):
            o = _dispatch.fused_paged_decode(q, kp, vp, table, vcount,
                                             backend=backend)
        else:
            o = attn_lib.decode_attention(
                q, _gather(kp), _gather(vp), length=lengths + 1,
                window=cfg.swa_window)
        a_out = o.reshape(B, 1, cfg.q_dim) @ lp["wo"]
        ys = {"k": kp, "v": vp}
        if cfg.ssm is not None:
            m_out, (h2, conv2) = ssm_lib.mamba_head(
                h, lp, cfg, state=(layer_in["ssm_h"], layer_in["ssm_conv"]))
            a_out = (a_out + m_out) * 0.5
            ys["ssm_h"], ys["ssm_conv"] = h2, conv2
        x = x + a_out
        m = mlp_block(x, lp, cfg)[0]
        return x + m, ys

    def _gather(pages):
        return pages[table].reshape(B, C, *pages.shape[2:])

    x, ys = jax.lax.scan(body, x, xs)
    x = rms_norm(x, params["final_norm"].astype(cfg.dtype), cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(cfg.dtype)
    logits = x @ head
    new_pool = dict(pool)
    new_pool["k_pages"], new_pool["v_pages"] = ys["k"], ys["v"]
    new_pool["lengths"] = lengths + active.astype(jnp.int32)
    if cfg.ssm is not None:
        new_pool["ssm_h"], new_pool["ssm_conv"] = ys["ssm_h"], ys["ssm_conv"]
    return logits, new_pool
