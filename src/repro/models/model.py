"""Model dispatcher: one API across all families.

    init_params(key, cfg)                        -> params pytree
    loss_fn(params, batch, cfg, remat=...)       -> (loss, metrics)
    forward(params, cfg, batch)                  -> logits
    init_cache / prefill / decode_step           -> serving path
    param_logical_axes(cfg)                      -> logical sharding tree
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import encdec, transformer, xlstm_stack
from repro.models.common import cross_entropy


def _mod(cfg: ModelConfig):
    if cfg.family == "encdec":
        return encdec
    if cfg.family == "xlstm":
        return xlstm_stack
    return transformer


def init_params(key, cfg: ModelConfig):
    return _mod(cfg).init_params(key, cfg)


def param_logical_axes(cfg: ModelConfig, model_size=None):
    return _mod(cfg).param_logical_axes(cfg, model_size)


def supports_segment_plan(cfg: ModelConfig) -> bool:
    """Whether this family's forward consumes a Tier-1.5 SegmentPlan (the
    stacked-layer transformer scan; encdec/xlstm keep whole-type Tier 1)."""
    return _mod(cfg) is transformer


def _forward(params, cfg: ModelConfig, batch: Dict[str, Any], *, remat: str,
             attn_args, plan):
    """(logits, aux, counts): ``counts`` the model's per-step work counts,
    a held-expert layer's ``expert_load`` (L, held experts), from the layer
    scan's outputs."""
    if cfg.family == "encdec":
        logits, aux = encdec.forward(params, cfg, batch["tokens"], batch["frames"],
                                     remat=remat, attn_args=attn_args)
    elif supports_segment_plan(cfg):
        return transformer.forward(params, cfg, batch["tokens"], remat=remat,
                                   attn_args=attn_args, plan=plan)
    else:
        logits, aux = _mod(cfg).forward(params, cfg, batch["tokens"], remat=remat,
                                        attn_args=attn_args)
    return logits, aux, {}


def forward(params, cfg: ModelConfig, batch: Dict[str, Any], *, remat: str = "none",
            attn_args=None, plan=None):
    return _forward(params, cfg, batch, remat=remat, attn_args=attn_args,
                    plan=plan)[:2]


def loss_fn(params, batch: Dict[str, Any], cfg: ModelConfig, *, remat: str = "none",
            attn_args=None, plan=None):
    """(loss, metrics); a model that routes to held experts adds their
    ``expert_load`` (L, held experts) to the metrics."""
    logits, aux, counts = _forward(params, cfg, batch, remat=remat,
                                   attn_args=attn_args, plan=plan)
    ce = cross_entropy(logits, batch["labels"])
    loss = ce + aux
    return loss, {"loss": loss, "ce": ce, "aux_loss": aux, **counts}


def init_cache(params, cfg: ModelConfig, batch: int, max_len: int):
    return _mod(cfg).init_cache(params, cfg, batch, max_len)


def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], max_len: int):
    if cfg.family == "encdec":
        return encdec.prefill(params, cfg, batch["tokens"], batch["frames"], max_len)
    return _mod(cfg).prefill(params, cfg, batch["tokens"], max_len)


def decode_step(params, cfg: ModelConfig, cache, tokens):
    return _mod(cfg).decode_step(params, cfg, cache, tokens)


def supports_paged(cfg: ModelConfig) -> bool:
    """Whether this family has the paged serving path (the stacked-layer
    transformer; encdec needs cross-attention state, xlstm has no KV cache)."""
    return _mod(cfg) is transformer


def init_paged_pool(cfg: ModelConfig, max_slots: int, max_len: int,
                    page_size: int, n_pages: int = 0):
    assert supports_paged(cfg), cfg.family
    return transformer.init_paged_pool(cfg, max_slots, max_len, page_size,
                                       n_pages)


def decode_step_paged(params, cfg: ModelConfig, pool, tokens, *, active=None,
                      attn_args=None):
    assert supports_paged(cfg), cfg.family
    return transformer.decode_step_paged(params, cfg, pool, tokens,
                                         active=active, attn_args=attn_args)
