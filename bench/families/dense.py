"""The dense decoder (Llama / Qwen3 block): one attention and one SwiGLU MLP
per layer, grouped-query heads, a head tied to the embedding or not.

Weights (``L`` layers stacked on the leading axis, ``d`` the hidden size,
``q = heads * head_dim``, ``kv = kv_heads * head_dim``):

    embed (V, d)      layers/attn_norm (L, d)   layers/wq (L, d, q)
    final_norm (d,)   layers/wk, wv (L, d, kv)  layers/wo (L, q, d)
    lm_head (d, V)    layers/mlp_norm (L, d)    layers/w_gate, w_up (L, d, f)
    (untied only)                               layers/w_down (L, f, d)

Work a step requires (``bench/flops.py`` says what "required" counts):

* every layer matrix and the head: ``2 * tokens`` FLOPs a weight for the
  forward pass, the same for dX, and the same for dW of the live rows of the
  layer matrices and of the head;
* causal attention: ``QK^T`` and ``PV`` over the ``S (S + 1) / 2`` causal
  pairs of each row, ``4 * heads * head_dim`` FLOPs a pair, and twice that
  for the backward pass (``dV``, ``dP``, ``dQ``, ``dK``).

Each matrix type is one monitor group of the program, ``layers/<type>``,
frozen per layer: a ``(L,)`` mask.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
F32, BF16 = 4, 2


def model_config(cell):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.config import ModelConfig
    c = cell.config
    return ModelConfig(
        name=c["name"], family="dense", n_layers=cell.n_layers,
        d_model=int(c["hidden_size"]), n_heads=int(c["num_attention_heads"]),
        n_kv_heads=int(c["num_key_value_heads"]),
        d_ff=int(c["intermediate_size"]), vocab=cell.vocab,
        head_dim=int(c["head_dim"]), rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        dtype=c["compute_dtype"], param_dtype=c["param_dtype"])


def shapes(config: Dict[str, Any]):
    """Leaf shapes with their fan-in axis (None for norm gains)."""
    L = int(config["num_hidden_layers"])
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    hd = int(config["head_dim"])
    q = int(config["num_attention_heads"]) * hd
    kv = int(config["num_key_value_heads"]) * hd
    V = int(config["vocab_size"])
    out = {
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


def frozen_masks(cell) -> Dict[str, np.ndarray]:
    """Monitor group -> its ``(L,)`` frozen flags written at set-up."""
    return {f"layers/{m}": np.asarray(rows, bool)
            for m, rows in cell.frozen_rows().items()}


def _matrix_sizes(cell) -> Dict[str, int]:
    """Weights of one layer's matrix of each type."""
    c = cell.config
    d, f = int(c["hidden_size"]), int(c["intermediate_size"])
    hd = int(c["head_dim"])
    q = int(c["num_attention_heads"]) * hd
    kv = int(c["num_key_value_heads"]) * hd
    return {"wq": d * q, "wk": d * kv, "wv": d * kv, "wo": q * d,
            "w_gate": d * f, "w_up": d * f, "w_down": f * d}


def _live_layers(cell) -> Dict[str, int]:
    """Layers in which each matrix type trains."""
    frozen = cell.frozen_rows()
    return {m: cell.n_layers - sum(frozen.get(m, [])) for m in MATRICES}


def attention_flops_per_call(cell) -> int:
    """One layer's ``QK^T`` plus ``PV`` over every row: the forward pass,
    and equally each of the two backward kernels' required pair of
    matmuls (``dP`` and ``dQ``; ``dV`` and ``dK``)."""
    c = cell.config
    return 4 * int(c["num_attention_heads"]) * int(c["head_dim"]) \
        * cell.causal_pairs


def step_flops(cell) -> int:
    """FLOPs one training step requires (see the module docstring)."""
    T = cell.tokens_per_step
    sizes, live = _matrix_sizes(cell), _live_layers(cell)
    head = int(cell.config["hidden_size"]) * cell.vocab
    all_w = cell.n_layers * sum(sizes.values())
    live_w = sum(sizes[m] * live[m] for m in MATRICES)
    dense = 2 * T * (all_w + head)          # forward
    dense += 2 * T * (all_w + head)         # dX
    dense += 2 * T * (live_w + head)        # dW
    return dense + 3 * cell.n_layers * attention_flops_per_call(cell)


def flash_bytes_per_call(cell) -> Dict[str, int]:
    """Least HBM traffic of one call of each flash kernel: every operand
    read once and every result written once (bf16 activations, f32
    log-sum-exp and ``D`` rows)."""
    c = cell.config
    T, hd = cell.tokens_per_step, int(c["head_dim"])
    q = T * int(c["num_attention_heads"]) * hd * BF16
    kv = T * int(c["num_key_value_heads"]) * hd * BF16
    row = T * int(c["num_attention_heads"]) * F32
    return {"flash_fwd": q + 2 * kv + q + row,
            "flash_dq": q + 2 * kv + q + 2 * row + q,
            "flash_dkv": q + 2 * kv + q + 2 * row + 2 * kv}


def grades_bytes_per_step(cell) -> Dict[str, int]:
    """Least HBM traffic of the freeze machinery's kernels in one step,
    over live rows only: ``grades_norm`` reads the f32 gradient and the bf16
    previous gradient and writes the latter (8 bytes a weight);
    ``masked_adamw`` reads the f32 weight, gradient and both moments and
    writes the weight and moments (28 bytes a weight)."""
    sizes, live = _matrix_sizes(cell), _live_layers(cell)
    n = sum(sizes[m] * live[m] for m in MATRICES)
    return {"grades_norm": 8 * n, "masked_adamw": 28 * n}


def frozen_share(cell) -> float:
    """Share of the monitored weights frozen at set-up."""
    sizes, live = _matrix_sizes(cell), _live_layers(cell)
    total = cell.n_layers * sum(sizes.values())
    return 1 - sum(sizes[m] * live[m] for m in MATRICES) / total
