"""Seeded weights of a dense decoder, made on the device in one jitted call.

The benchmark makes the weights; the program and the reference are each
handed them.  Layout (``L`` layers stacked on the leading axis, ``d`` the
hidden size, ``q = heads * head_dim``, ``kv = kv_heads * head_dim``):

    embed (V, d)      layers/attn_norm (L, d)   layers/wq (L, d, q)
    final_norm (d,)   layers/wk, wv (L, d, kv)  layers/wo (L, q, d)
    lm_head (d, V)    layers/mlp_norm (L, d)    layers/w_gate, w_up (L, d, f)
    (untied only)                               layers/w_down (L, f, d)

Matrices are truncated normals (two standard deviations) of standard
deviation ``1/sqrt(fan_in)``; norm gains are offsets from 1 and start at 0.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

Shapes = Dict[str, Any]


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole-number seed, also beyond 32 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)


def shapes(config: Dict[str, Any]) -> Shapes:
    """Leaf shapes with their fan-in axis (None for norm gains)."""
    L = int(config["num_hidden_layers"])
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    hd = int(config["head_dim"])
    q = int(config["num_attention_heads"]) * hd
    kv = int(config["num_key_value_heads"]) * hd
    V = int(config["vocab_size"])
    out: Shapes = {
        "embed": ((V, d), -1),
        "layers": {
            "attn_norm": ((L, d), None),
            "wq": ((L, d, q), -2), "wk": ((L, d, kv), -2),
            "wv": ((L, d, kv), -2), "wo": ((L, q, d), -2),
            "mlp_norm": ((L, d), None),
            "w_gate": ((L, d, f), -2), "w_up": ((L, d, f), -2),
            "w_down": ((L, f, d), -2),
        },
        "final_norm": ((d,), None),
    }
    if not config["tie_word_embeddings"]:
        out["lm_head"] = ((d, V), -2)
    return out


def _leaf(key, spec: Tuple, dtype) -> jax.Array:
    shape, fan_axis = spec
    if fan_axis is None:
        return jnp.zeros(shape, dtype)
    std = shape[fan_axis] ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def make(config: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights for ``seed``, in ``config["param_dtype"]``."""
    spec = shapes(config)
    dtype = jnp.dtype(config["param_dtype"])
    leaves, treedef = jax.tree.flatten(
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))

    @jax.jit
    def build(key):
        return jax.tree.unflatten(treedef, [
            _leaf(jax.random.fold_in(key, i), s, dtype)
            for i, s in enumerate(leaves)])

    return build(seed_key(seed))
