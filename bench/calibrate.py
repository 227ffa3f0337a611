#!/usr/bin/env python3
"""Readings that set the limits of ``correct`` for one cell (not run by the
benchmark itself).

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--faults 3]

For each seed, in one process on the chip at the cell's own size: the
program's first sync block (as a benchmark run drives it) against the plain
reference, which gives the lower readings; and on the first ``--faults``
seeds the control (the reference computed in float8 in the program's place)
and the program with a planted fault: ``half_batch`` (half of the rows left
out, the mean taken over the rest).  A step that returns its state unchanged
(``unchanged``, which the tests plant) reads 1 on ``grad_gap`` and
``change_gap`` by construction and needs no run.  One JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)


def half_batch(make):
    """Plant: the compiled block sees only the first half of each batch."""
    def planted(*a, **k):
        step = make(*a, **k)

        def broken(state, block):
            half = block["tokens"].shape[1] // 2
            return step(state, {n: v[:, :half] for n, v in block.items()})
        return broken
    return planted


def unchanged(make):
    """Plant: the compiled block returns the state it was given."""
    def planted(*a, **k):
        step = make(*a, **k)

        def broken(state, block):
            return state, step(state, block)[1]
        return broken
    return planted


FAULTS = {"half_batch": half_batch, "unchanged": unchanged}


def program_readings(cell, seed, fault=None):
    import data
    import program
    import weights
    import repro.train.loop as loop
    gen = data.NoisyPermutation(cell.vocab, cell.seq_len, cell.rows, seed,
                                float(cell.traffic["data"]["noise"]))
    orig = loop.make_multi_step
    if fault is not None:
        loop.make_multi_step = FAULTS[fault](orig)
    try:
        got = program.run(cell, seed, 0, gen,
                          lambda: weights.make(cell.config, seed),
                          first_block_only=True)
    finally:
        loop.make_multi_step = orig
    return dict(got["readings"], losses=got["losses"]), gen


def reference_readings(cell, seed, gen, precision="float32"):
    import weights
    from references import load
    batches = [gen.batch(i) for i in
               range(cell.traffic["trainer"]["sync_interval"])]
    return load(cell, precision).train(
        lambda: weights.make(cell.config, seed), batches)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", type=int, default=3)
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
        prog, gen = program_readings(cell, seed)
        faults = {}
        if i < args.faults:
            faults["half_batch"] = program_readings(cell, seed,
                                                    "half_batch")[0]
        ref = reference_readings(cell, seed, gen)
        emit("sound", seed, check.readings(prog, ref))
        for name, got in faults.items():
            emit(name, seed, check.readings(got, ref))
        if i < args.faults:
            ctl = reference_readings(cell, seed, gen, "fp8")
            emit("control", seed, check.readings(ctl, ref))
    return 0


if __name__ == "__main__":
    sys.exit(main())
