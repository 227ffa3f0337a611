"""Work a training step requires, worked out from the configuration, the
tokens and the cell's frozen rows.  Nothing here reads the program.

"Required" counts what the mathematics of one step needs, once:

* the forward pass: ``2 * tokens`` FLOPs per weight of every layer matrix
  and of the head;
* dX through every layer and the head (the embedding trains, so the
  gradient reaches the bottom): the same again;
* dW for the live rows of the layer matrices and for the head: the same
  again per live weight;
* causal attention: ``QK^T`` and ``PV`` over the ``S (S + 1) / 2`` causal
  pairs of each row, ``4 * heads * head_dim`` FLOPs a pair, and twice that
  for the backward pass (``dV``, ``dP``, ``dQ``, ``dK``).

Recomputation for memory (remat, the flash backward's score tiles) is not
required and not counted.  Norms, softmax and the optimizer are elementwise
and left out of FLOPs; the optimizer and monitor kernels are counted in HBM
bytes instead.
"""
from __future__ import annotations

from typing import Dict

from cell import Cell

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
F32, BF16 = 4, 2


def matrix_sizes(cell: Cell) -> Dict[str, int]:
    """Weights of one layer's matrix of each type."""
    c = cell.config
    d, f = int(c["hidden_size"]), int(c["intermediate_size"])
    hd = int(c["head_dim"])
    q = int(c["num_attention_heads"]) * hd
    kv = int(c["num_key_value_heads"]) * hd
    return {"wq": d * q, "wk": d * kv, "wv": d * kv, "wo": q * d,
            "w_gate": d * f, "w_up": d * f, "w_down": f * d}


def live_layers(cell: Cell) -> Dict[str, int]:
    """Layers in which each matrix type trains."""
    frozen = cell.frozen_rows()
    return {m: cell.n_layers - sum(frozen.get(m, [])) for m in MATRICES}


def tokens_per_step(cell: Cell) -> int:
    return cell.rows * cell.seq_len


def causal_pairs(cell: Cell) -> int:
    S = cell.seq_len
    return cell.rows * S * (S + 1) // 2


def attention_flops_per_call(cell: Cell) -> int:
    """One layer's ``QK^T`` plus ``PV`` over every row: the forward pass,
    and equally each of the two backward kernels' required pair of
    matmuls (``dP`` and ``dQ``; ``dV`` and ``dK``)."""
    c = cell.config
    return 4 * int(c["num_attention_heads"]) * int(c["head_dim"]) \
        * causal_pairs(cell)


def step_flops(cell: Cell) -> int:
    """FLOPs one training step requires (see the module docstring)."""
    T = tokens_per_step(cell)
    sizes, live = matrix_sizes(cell), live_layers(cell)
    head = int(cell.config["hidden_size"]) * cell.vocab
    all_w = cell.n_layers * sum(sizes.values())
    live_w = sum(sizes[m] * live[m] for m in MATRICES)
    dense = 2 * T * (all_w + head)          # forward
    dense += 2 * T * (all_w + head)         # dX
    dense += 2 * T * (live_w + head)        # dW
    return dense + 3 * cell.n_layers * attention_flops_per_call(cell)


def flash_bytes_per_call(cell: Cell) -> Dict[str, int]:
    """Least HBM traffic of one call of each flash kernel: every operand
    read once and every result written once (bf16 activations, f32
    log-sum-exp and ``D`` rows)."""
    c = cell.config
    T, hd = tokens_per_step(cell), int(c["head_dim"])
    q = T * int(c["num_attention_heads"]) * hd * BF16
    kv = T * int(c["num_key_value_heads"]) * hd * BF16
    row = T * int(c["num_attention_heads"]) * F32
    return {"flash_fwd": q + 2 * kv + q + row,
            "flash_dq": q + 2 * kv + q + 2 * row + q,
            "flash_dkv": q + 2 * kv + q + 2 * row + 2 * kv}


def grades_bytes_per_step(cell: Cell) -> Dict[str, int]:
    """Least HBM traffic of the freeze machinery's kernels in one step,
    over live rows only: ``grades_norm`` reads the f32 gradient and the bf16
    previous gradient and writes the latter (8 bytes a weight);
    ``masked_adamw`` reads the f32 weight, gradient and both moments and
    writes the weight and moments (28 bytes a weight)."""
    sizes, live = matrix_sizes(cell), live_layers(cell)
    n = sum(sizes[m] * live[m] for m in MATRICES)
    return {"grades_norm": 8 * n, "masked_adamw": 28 * n}


def frozen_share(cell: Cell) -> float:
    """Share of the monitored weights frozen at set-up."""
    sizes, live = matrix_sizes(cell), live_layers(cell)
    total = cell.n_layers * sum(sizes.values())
    return 1 - sum(sizes[m] * live[m] for m in MATRICES) / total
