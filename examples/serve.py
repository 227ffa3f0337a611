"""Batched serving: prefill a batch of prompts, then jitted decode steps with a
KV cache (rolling window for SWA archs, recurrent state for SSM/xLSTM).

    PYTHONPATH=src python examples/serve.py --arch mixtral-8x22b   # reduced cfg
    PYTHONPATH=src python examples/serve.py --continuous           # paged engine

The default mode is the fixed-batch loop (one prefill, decode to a shared
generation-length barrier); ``--continuous`` runs the same prompts through the
paged continuous-batching engine (``repro.serve``) instead.  Both warm up jit
before timing and report prefill latency separately from decode throughput —
compile time is never in the numbers.

The continuous path doubles as the serve-cell chaos CLI (DESIGN.md §5c):
``--inject-fault kind@tick[:arg]`` injects deterministic serve faults
(``nan_logits``/``engine_kill``/``slow_block``/``pool_leak``), ``--snapshot-dir``
enables block-boundary snapshot-resume (a SIGTERM drains, snapshots and exits
75 = EXIT_PREEMPTED; rerunning the identical command resumes bit-identically),
``--max-queue``/``--deadline-slack`` turn on bounded-queue admission with
deadline shedding, and ``--stream-out`` dumps the per-request token streams
and terminal statuses as JSON for recovery-invariant comparison.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.launch.cache import enable_compile_cache
import repro.configs as configs
from repro.models import model


def generate(params, cfg, prompts, max_new: int, temperature: float = 0.0,
             seed: int = 0):
    B, S = prompts.shape
    max_len = S + max_new
    prefill = jax.jit(
        lambda p, t: model.prefill(p, cfg, {"tokens": t}, max_len))

    @jax.jit
    def step(params, cache, tok, key):
        logits, cache = model.decode_step(params, cfg, cache, tok)
        nxt = (logits[:, -1].argmax(-1) if temperature == 0.0 else
               jax.random.categorical(key, logits[:, -1] / temperature))
        return cache, nxt[:, None].astype(jnp.int32)

    def run():
        t0 = time.perf_counter()
        logits, cache = prefill(params, prompts)
        tok = logits[:, -1:].argmax(-1).astype(jnp.int32)
        tok.block_until_ready()
        t_prefill = time.perf_counter() - t0
        key = jax.random.PRNGKey(seed)
        out = [tok]
        t0 = time.perf_counter()
        for _ in range(max_new - 1):
            key, sub = jax.random.split(key)
            cache, tok = step(params, cache, tok, sub)
            out.append(tok)
        toks = jnp.concatenate(out, axis=1)
        toks.block_until_ready()
        return toks, t_prefill, time.perf_counter() - t0

    run()                     # warm up prefill + decode step (compile)
    return run()              # timed


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the paged continuous-batching engine")
    ap.add_argument("--n-requests", type=int, default=0,
                    help="workload size (default 4 x batch)")
    ap.add_argument("--rate", type=float, default=2.0,
                    help="open-loop arrival rate (requests per tick)")
    ap.add_argument("--seed", type=int, default=0, help="workload seed")
    ap.add_argument("--block-steps", type=int, default=4,
                    help="decode steps fused per engine tick")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue depth (0 = unbounded)")
    ap.add_argument("--deadline-slack", default="",
                    help="lo,hi: attach deadline_tick = arrival + U[lo,hi] "
                         "to every request (enables shedding)")
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="kind@tick[:arg]",
                    help="deterministic serve fault (repeatable): nan_logits, "
                         "engine_kill, slow_block, pool_leak")
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--snapshot-dir", default="",
                    help="snapshot-resume directory (resumes if it holds a "
                         "valid snapshot)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="snapshot every N ticks (with --snapshot-dir)")
    ap.add_argument("--stream-out", default="",
                    help="write per-request streams + terminal statuses as "
                         "JSON (the recovery-invariant artifact)")
    args = ap.parse_args()

    cfg = configs.reduced(args.arch)
    params = model.init_params(jax.random.PRNGKey(0), cfg)

    if args.continuous:
        from repro.robustness.faults import FaultPlan, exit_code_for
        from repro.serve import ServeEngine, synthetic_workload
        if not model.supports_paged(cfg):
            sys.exit(f"--continuous needs the transformer serving path; "
                     f"{args.arch} is family {cfg.family}")
        slack = None
        if args.deadline_slack:
            lo, hi = (int(x) for x in args.deadline_slack.split(","))
            slack = (lo, hi)
        reqs = synthetic_workload(
            seed=args.seed, n_requests=args.n_requests or 4 * args.batch,
            rate=args.rate, prompt_lens=[args.prompt_len], vocab=cfg.vocab,
            max_new_range=(args.max_new // 2, args.max_new),
            deadline_slack=slack)
        plan = (FaultPlan.parse(args.inject_fault, seed=args.fault_seed)
                if args.inject_fault else None)
        eng = ServeEngine(params, cfg, max_slots=args.batch,
                          max_len=args.prompt_len + args.max_new,
                          block_steps=args.block_steps,
                          max_queue=args.max_queue or None,
                          snapshot_every=args.snapshot_every,
                          fault_plan=plan)
        streams, m = eng.run(reqs, snapshot_dir=args.snapshot_dir or None)
        print(f"arch={cfg.name} continuous [{m['stop']}"
              f"{', resumed' if m['resumed'] else ''}]: "
              f"{m['completed']}/{m['n_requests']} completed "
              f"(shed {m['shed']}, rejected {m['rejected']}, "
              f"failed {m['failed']}), "
              f"{m['total_new_tokens']} tokens in {m['run_wall_s']:.2f}s "
              f"({m['tok_s']:.1f} tok/s, "
              f"p99 latency {m['request_latency_s']['p99'] * 1e3:.0f}ms)")
        print(f"prefill latency p50 {m['prefill_latency_s']['p50'] * 1e3:.1f}ms, "
              f"queue depth p50/p99 {m['queue_depth']['p50']:.0f}/"
              f"{m['queue_depth']['p99']:.0f}" +
              (f", deadline hit rate {m['deadline_hit_rate']:.2f}"
               if m["deadline_hit_rate"] is not None else ""))
        if args.stream_out:
            with open(args.stream_out, "w") as f:
                json.dump({"streams": {str(k): v for k, v in streams.items()},
                           "statuses": {str(k): v
                                        for k, v in m["statuses"].items()},
                           "stop": m["stop"], "resumed": m["resumed"]}, f)
        sys.exit(exit_code_for(m["stop"]))

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    toks, t_prefill, t_decode = generate(params, cfg, prompts, args.max_new)
    n_decode = args.batch * (args.max_new - 1)
    print(f"arch={cfg.name} generated {toks.shape}: "
          f"prefill {t_prefill * 1e3:.1f}ms, "
          f"decode {n_decode / t_decode:.1f} tok/s (compile excluded)")
    print(toks[:2])


if __name__ == "__main__":
    main()
