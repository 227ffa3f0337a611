"""Mixture-of-experts block: top-k token-choice routing with sort/scatter dispatch.

Design note (DESIGN.md §4): the classical GShard dispatch is a one-hot einsum of
shape (tokens × experts × capacity) — at kimi-k2 scale (E=384) that einsum costs more
FLOPs than the experts themselves and poisons the roofline's useful-FLOPs ratio.  We
instead compute each routed token's slot by a cumsum rank over the one-hot assignment
(integer work, no matmul) and move tokens with scatter/gather:

    positions = rank of (token, k) within its expert   # cumsum over (T·k, E) one-hot
    buffer    = zeros(E, C, D).at[expert_idx, positions].add(token * keep)
    expert compute: batched (E, C, D) @ (E, D, F) einsums
    combine   = gather back + weighted sum over k

Experts are sharded over the "expert" logical axis (expert parallelism); tokens are
processed in groups of ``group_size`` so the scatter buffers stay small and the
dispatch is local to each data shard.

:func:`held_expert_block` is DeepSeek-V3's layer (``MoEConfig.scoring ==
"sigmoid"``) on the slice of experts one chip holds: it routes over all the
router's experts, drops nothing, and runs its held experts as grouped
matmuls over the assignments actually routed to them.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.config import MoEConfig
from repro.distributed.sharding import logical_constraint
from repro.kernels import resolve_interpret
from repro.models.mlp import swiglu

#: megablox tiles (rows, contraction, output columns) of the held experts'
#: grouped matmuls
GMM_TILING = (512, 512, 512)

#: parameter leaves that are buffers: the forward reads them and nothing
#: trains them, so they keep no optimizer moments and take no update or
#: weight decay (DeepSeek-V3's expert-selection correction bias)
BUFFERS = frozenset({"router_bias"})

#: leaves a sigmoid-scored layer keeps in float32 inside the layer body: the
#: router's, whose scores pick the experts
SIGMOID_FLOAT32_LEAVES = frozenset({"router"}) | BUFFERS


def capacity(group_tokens: int, cfg: MoEConfig) -> int:
    c = int(math.ceil(group_tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(c, cfg.top_k)


def route(x, router, cfg: MoEConfig):
    """x: (T, D) -> (weights (T,k), experts (T,k) int32, aux_losses)."""
    logits = (x @ router).astype(jnp.float32)                 # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # GShard aux losses: load balance + router z-loss.
    T = x.shape[0]
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.zeros((cfg.n_experts,)).at[experts.reshape(-1)].add(1.0) / (T * cfg.top_k)
    aux = cfg.n_experts * jnp.sum(me * ce) * cfg.aux_loss
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2) * cfg.router_z_loss
    return weights, experts, aux + z


def moe_block(x, params, cfg: MoEConfig, *, dispatch: str = "einsum"):
    """x: (B, S, D). params: router (D,E), w_gate/w_up (E,D,F), w_down (E,F,D).

    ``dispatch="einsum"`` is the GShard formulation: dispatch/combine one-hot
    einsums, which GSPMD partitions cleanly (tokens over "data", experts over
    "model", all-to-all inserted automatically).  ``dispatch="scatter"`` moves
    tokens with scatter/gather (zero dispatch FLOPs) but XLA's SPMD partitioner
    replicates scatters across the expert axis — it is the single-device-efficient
    path and the starting point for the shard_map-EP hillclimb (EXPERIMENTS §Perf).
    """
    B, S, D = x.shape
    tokens = x.reshape(B * S, D)
    g = min(cfg.group_size, B * S)
    assert (B * S) % g == 0, (B, S, g)
    groups = tokens.reshape((B * S) // g, g, D)

    def per_group_einsum(xg):
        w, e, aux = route(xg, params["router"], cfg)          # (g,k),(g,k)
        C = capacity(g, cfg)
        flat_e = e.reshape(-1)                                # (g·k,)
        onehot = jax.nn.one_hot(flat_e, cfg.n_experts, dtype=jnp.int32)
        ranks = jnp.cumsum(onehot, axis=0) - onehot
        pos = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
        keep = (pos < C).astype(xg.dtype)
        # (g, k, E, C) one-hots collapsed to (g, E, C) dispatch/combine tensors
        e_oh = jax.nn.one_hot(e, cfg.n_experts, dtype=xg.dtype)       # (g,k,E)
        c_oh = jax.nn.one_hot(pos.reshape(g, cfg.top_k), C, dtype=xg.dtype)
        keep2 = keep.reshape(g, cfg.top_k)
        combine = jnp.einsum("gk,gke,gkc->gec", w.astype(xg.dtype) * keep2,
                             e_oh, c_oh)
        dispatch_t = jnp.einsum("gk,gke,gkc->gec", keep2, e_oh, c_oh)
        buf = jnp.einsum("gec,gd->ecd", dispatch_t, xg)
        buf = logical_constraint(buf, ("expert", None, None))
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
        out_buf = logical_constraint(out_buf, ("expert", None, None))
        return jnp.einsum("gec,ecd->gd", combine, out_buf), aux

    def per_group(xg):
        w, e, aux = route(xg, params["router"], cfg)          # (g,k),(g,k)
        C = capacity(g, cfg)
        flat_e = e.reshape(-1)                                # (g·k,)
        onehot = jax.nn.one_hot(flat_e, cfg.n_experts, dtype=jnp.int32)
        ranks = jnp.cumsum(onehot, axis=0) - onehot           # rank within expert
        pos = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
        keep = pos < C
        # dispatch: scatter tokens into (E, C, D)
        xk = jnp.repeat(xg, cfg.top_k, axis=0) * keep[:, None].astype(xg.dtype)
        buf = jnp.zeros((cfg.n_experts, C, D), xg.dtype)
        buf = buf.at[flat_e, jnp.where(keep, pos, C - 1)].add(
            jnp.where(keep[:, None], xk, 0))
        buf = logical_constraint(buf, ("expert", None, None))
        # expert compute (batched over E; E is the expert-parallel axis)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
            * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
        out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
        out_buf = logical_constraint(out_buf, ("expert", None, None))
        # combine: gather each (token, k) result and weight it
        got = out_buf[flat_e, pos] * keep[:, None].astype(xg.dtype)
        got = got.reshape(g, cfg.top_k, D) * w[..., None].astype(xg.dtype)
        return got.sum(axis=1), aux

    fn = per_group_einsum if dispatch == "einsum" else per_group
    out, aux = jax.vmap(fn)(groups)
    return out.reshape(B, S, D), aux.mean()


def moe_block_ref(x, params, cfg: MoEConfig):
    """Dense loop-over-experts oracle (no capacity drops) for unit tests."""
    B, S, D = x.shape
    tokens = x.reshape(B * S, D)
    w, e, _ = route(tokens, params["router"], cfg)
    out = jnp.zeros_like(tokens)
    for ex in range(cfg.n_experts):
        h = jax.nn.silu(tokens @ params["w_gate"][ex]) * (tokens @ params["w_up"][ex])
        y = h @ params["w_down"][ex]
        weight = jnp.where(e == ex, w, 0.0).sum(axis=1)
        out = out + y * weight[:, None].astype(y.dtype)
    return out.reshape(B, S, D)


def sigmoid_route(x, router, bias, cfg: MoEConfig):
    """DeepSeek-V3 ``noaux_tc`` routing, in float32.

    x: (T, D); router: (D, E); bias: (E,), the correction bias.  Experts are
    *selected* by ``sigmoid(x·W_r) + bias``, top-``top_k`` over all ``E``, and
    *weighted* by the unbiased sigmoid scores of the chosen ones, normalised
    to sum to 1 (``norm_topk``) and scaled by ``routed_scale``.  The bias
    moves only the selection: no gradient reaches it.  Returns (weights
    (T, k) float32, experts (T, k) int32).
    """
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    choice = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scale, experts


def grouped_matmul(lhs, rhs, sizes):
    """Rows of ``lhs`` (M, K), sorted by group, times their group's ``rhs``
    (G, K, N), over the first ``sum(sizes)`` rows only (megablox ``gmm``,
    which beat ``jax.lax.ragged_dot`` on a TPU v5e at Moonlight's widths);
    later rows of the result are undefined."""
    m, k = lhs.shape
    tm, tk, tn = GMM_TILING
    tiling = (min(tm, m), min(tk, k), min(tn, rhs.shape[2]))
    return gmm(lhs, rhs, sizes, lhs.dtype, tiling,
               interpret=resolve_interpret(None))


def held_expert_block(x, params, cfg: MoEConfig):
    """DeepSeek-V3 expert layer on the experts this chip holds.

    x: (B, S, D).  params: ``router`` (D, E) and ``router_bias`` (E,) over all
    ``E = cfg.n_experts``; ``w_gate``/``w_up`` (E_h, D, F), ``w_down``
    (E_h, F, D) of the held experts ``[held_offset, held_offset + E_h)``;
    ``shared_gate``/``shared_up`` (D, F_s), ``shared_down`` (F_s, D).

    Each token's picks among the held experts are sorted by expert and run
    through three grouped matmuls sized by the counts actually routed, so
    the device work follows the assignments (plus at most a tile of padding
    an expert); no token is dropped.  Picks of experts held elsewhere add no
    term here.  The shared experts are added for every token.  Returns
    (out (B, S, D), load (E_h,) int32: tokens x picks routed to each held
    expert).
    """
    B, S, D = x.shape
    T, k, E_h = B * S, cfg.top_k, cfg.held
    xt = x.reshape(T, D)
    weights, experts = sigmoid_route(xt, params["router"],
                                     params["router_bias"], cfg)
    local = experts.reshape(-1) - cfg.held_offset
    local = jnp.where((local >= 0) & (local < E_h), local, E_h)
    load = jnp.zeros((E_h + 1,), jnp.int32).at[local].add(1)[:E_h]
    # held picks first, by expert: at most min(k, E_h) a token are held
    M = T * min(k, E_h)
    M = -(-M // GMM_TILING[0]) * GMM_TILING[0] if M > GMM_TILING[0] else M
    order = jnp.argsort(local, stable=True)
    if M > order.shape[0]:
        order = jnp.pad(order, (0, M - order.shape[0]))
    order = order[:M]
    valid = (jnp.arange(M) < load.sum())[:, None]
    tok = order // k
    # rows past the routed assignments are undefined in every grouped
    # matmul, forward and backward: the selects keep them out of both
    xs = jnp.where(valid, xt[tok], 0).astype(x.dtype)
    h = jax.nn.silu(grouped_matmul(xs, params["w_gate"], load)) \
        * grouped_matmul(xs, params["w_up"], load)
    y = jnp.where(valid, grouped_matmul(h, params["w_down"], load), 0)
    y = y * weights.reshape(-1)[order].astype(y.dtype)[:, None]
    routed = jnp.zeros((T, D), y.dtype).at[tok].add(y)
    out = routed.reshape(B, S, D)
    if cfg.shared_d_ff:
        out = out + swiglu(x, params["shared_gate"], params["shared_up"],
                           params["shared_down"])
    return out, load
