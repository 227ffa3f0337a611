#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names its configuration and traffic mix, whose
files under ``bench/`` say everything about it (``bench/cell.py``).  A run:

1. makes the weights and the token stream from ``--seed``;
2. drives the program's ``Trainer`` through its first sync block and on
   into the measured window (``bench/program.py``); with ``--trace 1`` the
   window is traced by JAX's profiler;
3. frees the program's state and runs the plain reference over the first
   block's weights and batches, and compares (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted`` and ``failed`` (steps in the window, and those whose loss was
not finite), ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, each read by ``bench/metrics/<name>.py``),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``: each
compared number beside its limit, which also end standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
# libtpu logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

#: where runs keep JAX's compilation cache and the profiler's trace, inside
#: the checkout (a fixed path: the cache key holds it)
CACHE_DIR = os.path.join(BENCH, ".jax_cache")
TRACE_DIR = os.path.join(BENCH, ".trace")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def device_peaks(kind: str):
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json ({sorted(table)})")
    return table[kind]


def setup_jax():
    """JAX with its persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def metric_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench, cell_name: str, kind: str):
    """The cell's metric entries of ``kind`` ("end_to_end"/"per_layer")."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


class Tracer:
    """JAX's profiler around the window; the window itself is a host
    annotation the trace reduction looks for."""

    def __init__(self, directory: str):
        self.dir = directory
        self._span = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def open(self):
        import jax
        from trace_reduce import WINDOW_SPAN
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def close(self):
        self._span.__exit__(None, None, None)

    def stop(self):
        import jax
        jax.profiler.stop_trace()


def run_cell(cell, seed: int, seconds: float, trace: bool, bench,
             devices, peaks=None):
    """One run of ``cell``; returns the result line as a dict."""
    import check
    import data
    import program
    import weights
    from references import load as load_reference

    gen = data.NoisyPermutation(cell.vocab, cell.seq_len, cell.rows, seed,
                                float(cell.traffic["data"]["noise"]))
    params_fn = lambda: weights.make(cell.config, seed)  # noqa: E731
    tracer = Tracer(TRACE_DIR) if trace else None
    got = program.run(cell, seed, seconds, gen, params_fn, tracer=tracer)
    win = got["window"]
    setup_s = win.t0 - T_START
    window_s = win.t1 - win.t0
    steps = win.s1 - win.s0
    tokens = steps * cell.rows * cell.seq_len
    losses = got["window_losses"]
    failed = sum(1 for x in losses if not math.isfinite(x))
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(got["memory_peak_bytes"])}
    ctx = {"cell": cell, "steps": steps, "window_s": window_s,
           "compiles": got["compiles_in_window"], "peaks": peaks,
           "trace": None}
    line = {}
    if trace:
        import trace_reduce
        ctx["trace"] = trace_reduce.load(TRACE_DIR, n_chips=len(devices))
        device["busy_s"] = ctx["trace"].busy_s()
        device["window_s"] = ctx["trace"].window_s
        line["breakdown"] = ctx["trace"].breakdown()
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    prog = dict(got["readings"], losses=got["losses"])
    del got
    gc.collect()

    e2e = {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s}
    metrics = {}
    if trace:
        for m in cell_metrics(bench, cell.name, "per_layer"):
            value = metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell_metrics(bench, cell.name, "end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    batches = [gen.batch(i) for i in range(cell.traffic["trainer"]
                                            ["sync_interval"])]
    t_ref = time.perf_counter()
    ref = load_reference(cell).train(params_fn, batches)
    print(f"bench: set-up {setup_s:.1f} s, window {window_s:.1f} s "
          f"({steps} steps), reference {time.perf_counter() - t_ref:.1f} s",
          file=sys.stderr)
    numbers = check.readings(prog, ref)
    limits = cell.pair["limits"]
    checks = {k: {"value": check.printable(numbers[k]), "limit": limits[k]}
              for k in check.NUMBERS}
    out = {"correct": check.judge(numbers, limits), "attempted": steps,
           "failed": failed, "metrics": metrics, "device": device}
    out.update(line)
    out["checks"] = checks
    return out, numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    jax = setup_jax()
    from cell import load_cell
    cell = load_cell(args.workload)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    peaks = device_peaks(devices[0].device_kind)
    out, numbers = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                            bench, devices, peaks)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r}"
              + (f", at {numbers[k + '_at']}" if numbers.get(k + "_at")
                 else "") + ")", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
