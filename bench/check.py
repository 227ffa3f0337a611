"""The comparison that decides ``correct``.

The program's first sync block and the plain reference's run over the same
weights and batches give, for each step, the loss, and after the block, per
parameter leaf, the norm of the change and the square root of the sum of
AdamW's second moment (the gradients as the optimizer got them), and per
monitored row the GradES monitor.  Compared, each against its limit:

* ``loss_gap``: the largest absolute gap of a step's loss;
* ``grad_gap``, ``change_gap``: by the worst leaf, the gap between the
  program's norm and the reference's, over the reference's norm of that
  leaf or of the median leaf, whichever is larger.  Leaves whose reference
  gradient is under a thousandth of the median leaf's are left out: they are
  frozen, or move by round-off alone;
* ``monitor_gap``: the same, by the worst live monitored row: ``name[l]``
  of a group frozen per layer, ``name[l,e]`` per layer and expert;
* ``frozen_moved``: the largest change of a weight in a frozen row, which
  must be exactly 0.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "monitor_gap",
           "frozen_moved")


def _worst(prog: Dict[str, float], ref: Dict[str, float],
           keys: List[str]) -> Tuple[float, str]:
    if not keys:
        return 0.0, ""
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def readings(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """Every compared number, with the leaf or row that set it."""
    grads = [g for g in ref["grad"].values() if g > 0]
    floor = 1e-3 * float(np.median(grads)) if grads else 0.0
    leaves = sorted(k for k, g in ref["grad"].items() if g >= floor and g > 0)
    out: Dict[str, Any] = {}
    out["loss_gap"] = float(np.max(np.abs(
        np.asarray(prog["losses"]) - np.asarray(ref["losses"]))))
    out["grad_gap"], out["grad_gap_at"] = _worst(prog["grad"], ref["grad"],
                                                 leaves)
    out["change_gap"], out["change_gap_at"] = _worst(
        prog["change"], ref["change"], leaves)
    pm, rm = {}, {}
    for name, rows in ref["monitor"].items():
        rows, got = np.asarray(rows), np.asarray(prog["monitor"][name])
        for at in np.ndindex(rows.shape):
            if rows[at] > 0:
                key = f"{name}[{','.join(map(str, at))}]"
                pm[key], rm[key] = float(got[at]), float(rows[at])
    out["monitor_gap"], out["monitor_gap_at"] = _worst(pm, rm, sorted(rm))
    out["frozen_moved"] = max(prog.get("frozen_moved", {}).values(),
                              default=0.0)
    out["left_out"] = sorted(set(ref["grad"]) - set(leaves))
    return out


def judge(numbers: Dict[str, Any], limits: Dict[str, float]) -> bool:
    """Every number within its limit (a non-finite number is not)."""
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def printable(x: float):
    """A reading for a JSON line: a non-finite one as its name."""
    return x if np.isfinite(x) else str(x)
