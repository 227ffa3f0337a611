#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` for a cell of one row a step
(not run by the benchmark itself), where ``calibrate.py``'s ``half_batch``
fault has no second row to leave out.

    python3 bench/calibrate_one_row.py --workload <cell> --seeds 1 2 3 ... \\
        [--faults 3] [--controls 3]

As ``calibrate.py``, in one process on the chip at the cell's own size: for
each seed the program's first sync block against the plain reference, which
gives the lower readings; on the first ``--faults`` seeds the program with
the planted fault ``half_seq`` (each row's first half of its tokens alone,
the mean taken over them); on the first ``--controls`` seeds the control
(the reference computed in float8 in the program's place).  One JSON line a
reading, as ``calibrate.py`` writes them.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import calibrate  # noqa: E402


def half_seq(make):
    """Plant: the compiled block sees only the first half of each row."""
    def planted(*a, **k):
        step = make(*a, **k)

        def broken(state, block):
            half = block["tokens"].shape[2] // 2
            return step(state, {n: v[:, :, :half] for n, v in block.items()})
        return broken
    return planted


def half_seq_readings(cell, seed):
    """The program's readings with :func:`half_seq` planted."""
    import repro.train.loop as loop
    orig = loop.make_multi_step
    loop.make_multi_step = half_seq(orig)
    try:
        return calibrate.program_readings(cell, seed)[0]
    finally:
        loop.make_multi_step = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--controls", type=int, default=3)
    args = ap.parse_args(argv)
    import run
    run.setup_jax()
    import check
    from cell import load_cell
    cell = load_cell(args.workload)

    def emit(kind, seed, numbers):
        print(json.dumps({"workload": cell.name, "kind": kind, "seed": seed,
                          **numbers}), flush=True)

    for i, seed in enumerate(args.seeds):
        prog, gen = calibrate.program_readings(cell, seed)
        planted = half_seq_readings(cell, seed) if i < args.faults else None
        ref = calibrate.reference_readings(cell, seed, gen)
        emit("sound", seed, check.readings(prog, ref))
        if planted is not None:
            emit("half_seq", seed, check.readings(planted, ref))
        if i < args.controls:
            ctl = calibrate.reference_readings(cell, seed, gen, "fp8")
            emit("control", seed, check.readings(ctl, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
