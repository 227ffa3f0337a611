"""Benchmark entrypoint: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run``  prints ``name,us_per_call,derived``
CSV rows (plus writes full JSON/CSV artifacts under artifacts/bench/).
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=240)
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from benchmarks import (bench_ablation, bench_accuracy, bench_convergence,
                            bench_efficiency)

    print("name,us_per_call,derived")

    def emit(name, us, derived):
        print(f"{name},{us},{derived}", flush=True)

    want = lambda n: not args.only or args.only in n

    if want("table1"):
        for r in bench_accuracy.run(steps=args.steps):
            emit(f"table1/{r['method']}", round(r["wall_s"] * 1e6, 0),
                 f"acc={r['accuracy']} steps={r['steps_run']} stop={r['stop']}")

    if want("table4"):
        for r in bench_efficiency.run(steps=args.steps):
            emit(f"table4/{r['method']}", round(r["wall_s"] * 1e6, 0),
                 f"speedup={r['speedup']}x flops_ratio={r['flops_ratio']}")

    if want("table6"):
        for r in bench_ablation.run(steps=max(args.steps // 2, 60)):
            emit(f"table6/tau={r['tau']}/alpha={r['alpha']}",
                 round(r["wall_s"] * 1e6, 0),
                 f"acc={r['accuracy']} frozen={r['final_frozen_frac']:.2f}")

    if want("fig1"):
        rs = bench_convergence.run(steps=args.steps)
        emit("fig1/convergence", 0,
             f"final_loss={rs[-1]['loss']:.3f} frozen={rs[-1]['frozen_frac']:.2f}")

    if want("serve"):
        from benchmarks import bench_serve
        for p in bench_serve.run()["points"]:
            emit(f"serve/{p['arch']}/rate={p['rate_req_per_block']}", 0,
                 f"tok_s={p['continuous']['tok_s']} "
                 f"vs_fixed={p['speedup']}x "
                 f"p99_s={p['continuous']['request_latency_s']['p99']:.3f}")


if __name__ == "__main__":
    main()
