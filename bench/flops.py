"""Work a training step requires, worked out from the configuration, the
tokens and the cell's frozen rows.  Nothing here reads the program.

"Required" counts what the mathematics of one step needs, once:

* the forward pass: ``2 * tokens`` FLOPs per weight of every layer matrix
  and of the head;
* dX through every layer and the head (the embedding trains, so the
  gradient reaches the bottom): the same again;
* dW for the live rows of the layer matrices and for the head: the same
  again per live weight;
* attention's own matmuls over the causal pairs of each row, forward and
  backward.

Recomputation for memory (remat, the flash backward's score tiles) is not
required and not counted.  Norms, softmax and the optimizer are elementwise
and left out of FLOPs; the optimizer and monitor kernels are counted in HBM
bytes instead.

Which matrices a layer has, and how wide, is the model family's
(``bench/families/``): each count below hands on to the cell's family.
"""
from __future__ import annotations

from typing import Dict

from cell import Cell


def attention_flops_per_call(cell: Cell) -> int:
    """Required FLOPs of one call of a flash kernel: one layer's attention
    matmuls over every row."""
    return cell.family.attention_flops_per_call(cell)


def step_flops(cell: Cell) -> int:
    """FLOPs one training step requires (see the module docstring)."""
    return cell.family.step_flops(cell)


def flash_bytes_per_call(cell: Cell) -> Dict[str, int]:
    """Least HBM bytes of one call of each flash kernel."""
    return cell.family.flash_bytes_per_call(cell)


def grades_bytes_per_step(cell: Cell) -> Dict[str, int]:
    """Least HBM bytes of the freeze machinery's kernels in one step, over
    live rows only."""
    return cell.family.grades_bytes_per_step(cell)


def frozen_share(cell: Cell) -> float:
    """Share of the monitored weights frozen at set-up."""
    return cell.family.frozen_share(cell)
