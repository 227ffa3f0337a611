#!/usr/bin/env python3
"""Measure how far a cell's runs spread (not run by the benchmark itself).

    python3 bench/sets.py --workload <cell> --seconds <s> --sets 2 \
        --seeds <n> ... [--trace 0] [--out runs.jsonl]

Runs ``bench/run.py`` once per seed, the seeds in the same order in every
set, one process at a time, and appends one JSON line per run to ``--out``:
the cell, set, seed, ``--trace``, exit code, wall seconds and the run's result
line.  Then prints, per set and metric, the median and the spread: the
distance between the first and third quartiles that
``statistics.quantiles(values, n=4)`` gives, over the median.  A bound is set
from the widest spread of a metric over the sets and cells.  Stops at the
first run that fails or is not correct.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def summary(rows):
    """``{(set, metric): (median, spread, values)}`` over the runs that
    printed a result."""
    out = {}
    for s in sorted({r["set"] for r in rows}):
        lines = [r["line"] for r in rows if r["set"] == s and r["line"]]
        for name in sorted({m for ln in lines for m in ln["metrics"]}):
            v = [ln["metrics"][name]["value"] for ln in lines
                 if name in ln["metrics"]]
            if len(v) >= 2:
                out[(s, name)] = (statistics.median(v), spread(v), v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    rows = []
    for s in range(1, args.sets + 1):
        for seed in args.seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True)
            last = (p.stdout.strip().splitlines() or [""])[-1]
            row = {"cell": args.workload, "set": s, "seed": seed,
                   "trace": args.trace, "rc": p.returncode,
                   "wall": time.perf_counter() - t0,
                   "line": json.loads(last) if last.startswith("{") else None}
            rows.append(row)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            tail = [ln for ln in p.stderr.splitlines()
                    if ln.startswith(("bench:", "check "))]
            print(f"set {s} seed {seed} rc={p.returncode} "
                  f"wall={row['wall']:.1f} s correct="
                  f"{(row['line'] or {}).get('correct')}", *tail[-6:],
                  sep="\n  ", flush=True)
            if row["line"] is None or row["line"]["correct"] is not True:
                print(p.stderr[-3000:], flush=True)
                return 1
    for (s, name), (med, sp, v) in summary(rows).items():
        print(f"set {s} {name}: median {med!r} spread {sp:.4%} values {v}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
