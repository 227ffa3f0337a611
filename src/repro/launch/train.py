"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \
        --steps 100 --grades-tau 4e-3

On a real TPU cluster this process runs once per host (jax.distributed
initialization is env-driven); the mesh comes from launch/mesh.py and every
(arch × shape) from the assignment is selectable via --arch/--shape.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import jax

import repro.configs as configs
from repro.config import SHAPES, GradESConfig, LoRAConfig, TrainConfig
from repro.distributed.sharding import use_mesh
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import (make_dp_mesh, make_production_mesh,
                               rules_for)
from repro.robustness.faults import FaultPlan, exit_code_for
from repro.train.loop import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU dev); default is the full arch")
    ap.add_argument("--shape", choices=list(SHAPES), default=None,
                    help="use an assigned shape cell for seq/batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--lora-rank", type=int, default=0)
    ap.add_argument("--optimizer", default="adamw", choices=["adamw", "sgd"])
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--grades", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--grades-tau", type=float, default=4e-3)
    ap.add_argument("--grades-alpha", type=float, default=0.5)
    ap.add_argument("--grades-monitor", default="delta",
                    choices=["delta", "norm_delta"])
    ap.add_argument("--val-es", action="store_true",
                    help="classic validation early stopping baseline")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--mesh", choices=["none", "local", "single", "multi"],
                    default="none",
                    help="local = pure data parallel over the devices "
                         "present; single/multi = the 256/512-chip pod "
                         "meshes")
    ap.add_argument("--kernels", default="auto", choices=["auto", "pallas", "jnp"],
                    help="hot-path backend for the fused GradES kernels AND "
                         "flash attention; auto = Pallas on TPU (shard-mapped "
                         "over the mesh), jnp elsewhere")
    ap.add_argument("--sync-interval", type=int, default=8,
                    help="host sync boundary: steps per compiled lax.scan "
                         "block (1 = per-step host loop; DESIGN.md §4)")
    ap.add_argument("--prefetch-depth", type=int, default=2,
                    help="batch blocks staged ahead by the background "
                         "prefetch thread (0 = synchronous, no thread)")
    ap.add_argument("--segment-max", type=int, default=8,
                    help="Tier-1.5 segment cap: max per-layer freeze segments "
                         "the layer scan splits into (bounds recompiles at "
                         "segment_max * n_types; 1 = whole-type Tier 1 only)")
    ap.add_argument("--grad-compression", default="none",
                    choices=["none", "int8_ef"],
                    help="int8 error-feedback compression of the cross-pod "
                         "gradient leg (4x bytes on surviving leaves; "
                         "DESIGN.md §4)")
    ap.add_argument("--reduce-mode", default="auto",
                    choices=["auto", "explicit", "implicit"],
                    help="freeze-aware explicit DP gradient reduce: auto = "
                         "engage on an eligible pure-DP mesh, explicit = "
                         "require it (error when ineligible), implicit = "
                         "always keep the GSPMD all-reduce (DESIGN.md §3)")
    ap.add_argument("--attn-chunk-threshold", type=int, default=0,
                    help="override ModelConfig.attn_chunk_threshold (seq len "
                         "where the jnp fallback switches full -> blockwise)")
    ap.add_argument("--log", default="")
    # --- robustness / chaos (DESIGN.md §4) ---
    ap.add_argument("--inject-fault", action="append", default=[],
                    metavar="KIND@STEP[:ARG]",
                    help="deterministic fault injection (repeatable): kinds "
                         "kill, sigterm, nan_grad, inf_grad, ckpt_corrupt, "
                         "io_error, straggler, comm_corrupt — e.g. "
                         "nan_grad@40:2.0, ckpt_corrupt@16:bitflip, kill@20, "
                         "comm_corrupt@12 (needs --grad-compression int8_ef)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed keying every fault-plan random choice (victim "
                         "matrix / leaf / bit); same seed => same faults")
    ap.add_argument("--numerics-guard", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="all-finite sentinel on every block + boundary "
                         "rollback with LR backoff on a non-finite step")
    ap.add_argument("--rollback-lr-backoff", type=float, default=0.5,
                    help="multiplicative LR factor applied per guard rollback")
    ap.add_argument("--max-rollbacks", type=int, default=3,
                    help="guard trips beyond this abort the run "
                         "(exit code 77)")
    ap.add_argument("--straggler-abort", type=float, default=0.0,
                    help="p95/EMA per-step ratio past which the watchdog "
                         "checkpoints and aborts resumable (exit code 76; "
                         "0 = log only)")
    ap.add_argument("--prefetch-retries", type=int, default=3,
                    help="bounded retries for transient batch-read I/O errors")
    ap.add_argument("--prefetch-stall-timeout", type=float, default=0.0,
                    help="seconds next() waits on the prefetch worker before "
                         "raising PrefetchStalled (0 = wait forever)")
    ap.add_argument("--keep-checkpoints", type=int, default=3,
                    help="boundary checkpoints retained on disk (older ones "
                         "are GC'd; raise for bit-identity audits that diff "
                         "every boundary)")
    # --- elastic fleet handshake (DESIGN.md §4b) ---
    ap.add_argument("--worker-id", type=int, default=0,
                    help="rank within an elastic fleet (0 = chief, which "
                         "hosts the devices; >0 = heartbeat-only follower)")
    ap.add_argument("--world-size", type=int, default=0,
                    help="fleet size; >0 runs under an elastic coordinator: "
                         "the chief trains on a pure-DP fleet mesh of this "
                         "width, followers idle in follower_main")
    ap.add_argument("--fleet-dir", default="",
                    help="fleet rendezvous dir (heartbeats + stop files); "
                         "required when --world-size is set")
    args = ap.parse_args()
    enable_compile_cache()

    if args.world_size > 0 and not args.fleet_dir:
        ap.error("--world-size requires --fleet-dir")
    if args.world_size > 0 and args.worker_id > 0:
        # Followers never build a model or touch the device runtime — they
        # heartbeat and honor the drain protocol (elastic/worker.py).
        from repro.elastic.worker import follower_main
        sys.exit(follower_main(args.fleet_dir, args.worker_id,
                               args.world_size))

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    if args.attn_chunk_threshold:
        cfg = dataclasses.replace(cfg,
                                  attn_chunk_threshold=args.attn_chunk_threshold)
    seq, batch = args.seq, args.batch
    if args.shape:
        cell = SHAPES[args.shape]
        seq, batch = cell.seq_len, cell.global_batch
    tcfg = TrainConfig(
        seq_len=seq, global_batch=batch, steps=args.steps, lr=args.lr,
        optimizer=args.optimizer, remat=args.remat, kernels=args.kernels,
        sync_interval=args.sync_interval, prefetch_depth=args.prefetch_depth,
        segment_max=args.segment_max,
        grad_compression=args.grad_compression, reduce_mode=args.reduce_mode,
        lora=LoRAConfig(rank=args.lora_rank) if args.lora_rank else None,
        val_es=args.val_es,
        checkpoint_dir=args.ckpt, checkpoint_every=args.ckpt_every,
        grades=GradESConfig(enabled=args.grades, tau=args.grades_tau,
                            alpha=args.grades_alpha, normalize=True,
                            monitor=args.grades_monitor, patience=2),
        numerics_guard=args.numerics_guard,
        rollback_lr_backoff=args.rollback_lr_backoff,
        max_rollbacks=args.max_rollbacks,
        straggler_p95_abort=args.straggler_abort,
        prefetch_retries=args.prefetch_retries,
        prefetch_stall_timeout=args.prefetch_stall_timeout,
        fault_plan=(FaultPlan.parse(args.inject_fault, seed=args.fault_seed)
                    if args.inject_fault else None),
        keep_checkpoints=args.keep_checkpoints,
    )
    hb = None
    if args.world_size > 0:  # chief of an elastic fleet: publish heartbeats
        from repro.elastic.heartbeat import HeartbeatWriter
        hb = HeartbeatWriter(args.fleet_dir, 0)
    trainer = Trainer(cfg, tcfg, log_every=10, log_path=args.log or None,
                      progress_cb=hb.update if hb is not None else None)

    def run():
        val = None
        if args.val_es:
            from repro.data.pipeline import make_batches
            val = list(make_batches(cfg, tcfg, steps=4, seed_offset=777))
        return trainer.train(val_batches=val)

    if args.world_size > 0:
        mesh = make_dp_mesh(args.world_size)
        with use_mesh(mesh, rules_for(mesh)), hb:
            res = run()
    elif args.mesh != "none":
        mesh = (make_dp_mesh() if args.mesh == "local" else
                make_production_mesh(multi_pod=(args.mesh == "multi")))
        with use_mesh(mesh, rules_for(mesh)):
            res = run()
    else:
        res = run()
    print(json.dumps({
        "arch": cfg.name, "stop": res.stop_reason, "steps": res.steps_run,
        "wall_s": round(res.wall_time, 2), "recompiles": res.recompiles,
        "rollbacks": res.rollbacks,
        "final": res.history[-1] if res.history else None}, indent=1))
    # Resumable failures get distinct exit codes (75 preempted, 76 straggler,
    # 77 non-finite) so a supervisor can tell "relaunch me" from success.
    sys.exit(exit_code_for(res.stop_reason))


if __name__ == "__main__":
    main()
