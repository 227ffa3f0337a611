"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers, compiles,
and fits — and extract the roofline terms from the compiled artifact.

MUST be run as its own process (``python -m repro.launch.dryrun ...``): the
environment lines below execute before jax is imported, so the 512 placeholder
devices exist before jax initializes.  The process — and every ``--all``
child, which inherits the environment — is pinned to the CPU backend, so the
dry run never takes an accelerator that another process needs.  ``--all``
orchestrates one subprocess per cell (compiles are independent; parallelism
via --jobs).

Per cell:
  jax.jit(step_fn, in_shardings, out_shardings, donate).lower(*specs).compile()
  -> memory_analysis()   (bytes/device: proves it fits)
  -> cost_analysis()     (FLOPs / bytes for the roofline)
  -> compiled HLO text   (collective bytes for the roofline)
Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

import argparse
import dataclasses
import json
import subprocess
import sys
import time
from typing import Dict, Optional

import jax

import repro.configs as configs
from repro.config import SHAPES, shape_applicable
from repro.core.grades import build_monitor_spec
from repro.launch import roofline as rf
from repro.launch.mesh import chips as mesh_chips
from repro.launch.mesh import make_production_mesh, rules_for
from repro.launch.specs import (dryrun_model_cfg, dryrun_train_cfg,
                                serve_cell_specs, train_cell_specs)
from repro.distributed.sharding import use_mesh
from repro.models import model
from repro.train.step import make_train_step


def run_cell(arch: str, shape: str, mesh_name: str, out_dir: str,
             verbose: bool = True, variant: str = "opt") -> Dict:
    cell = SHAPES[shape]
    cfg = dryrun_model_cfg(configs.get(arch), seq_parallel=(variant == "opt"))
    ok, why = shape_applicable(cfg, cell)
    if not ok:
        rec = {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "skip",
               "reason": why}
        _write(out_dir, rec)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    rules = rules_for(mesh)
    if variant == "opt" and cell.kind == "decode":
        from repro.distributed.sharding import (DECODE_RULES,
                                                MULTIPOD_DECODE_RULES)
        rules = (MULTIPOD_DECODE_RULES if mesh_name == "multi" else DECODE_RULES)
    t0 = time.time()
    with use_mesh(mesh, rules):
        if cell.kind == "train":
            tcfg = dryrun_train_cfg(cfg, cell,
                                    microbatch=(variant == "opt"))
            state_sds, batch_sds, state_sh, batch_sh = train_cell_specs(
                cfg, tcfg, mesh, rules=rules)
            spec = build_monitor_spec(state_sds.params)
            step = make_train_step(cfg, tcfg, spec)
            fn = jax.jit(step, in_shardings=(state_sh, batch_sh),
                         out_shardings=(state_sh, None), donate_argnums=0)
            lowered = fn.lower(state_sds, batch_sds)
        elif cell.kind == "prefill":
            params_sds, params_sh, args_sds, args_sh, _, _ = serve_cell_specs(
                cfg, cell, mesh, rules=rules)

            def prefill_fn(params, args):
                return model.prefill(params, cfg, args, cell.seq_len)

            fn = jax.jit(prefill_fn, in_shardings=(params_sh, args_sh))
            lowered = fn.lower(params_sds, args_sds)
        else:  # decode
            (params_sds, params_sh, tok_sds, tok_sh, cache_sds,
             cache_sh) = serve_cell_specs(cfg, cell, mesh, rules=rules)

            def decode_fn(params, cache, tok):
                return model.decode_step(params, cfg, cache, tok)

            fn = jax.jit(decode_fn,
                         in_shardings=(params_sh, cache_sh, tok_sh),
                         out_shardings=(None, cache_sh),
                         donate_argnums=1)
            lowered = fn.lower(params_sds, cache_sds, tok_sds)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    bytes_per_chip = getattr(mem, "temp_size_in_bytes", 0) + \
        getattr(mem, "argument_size_in_bytes", 0)
    hlo = compiled.as_text()
    if os.environ.get("DRYRUN_SAVE_HLO"):
        os.makedirs(os.path.join(out_dir, "hlo"), exist_ok=True)
        with open(os.path.join(out_dir, "hlo",
                               f"{arch}__{shape}__{mesh_name}.txt"), "w") as f:
            f.write(hlo)
    # The only dynamic-trip loop in the zoo is the causal kv-block loop of the
    # blockwise attention (prefill >8k): average trips ~= n_kv_blocks / 2.
    dyn_trip = max(1.0, cell.seq_len / 1024 / 2) if cell.kind == "prefill" else 1.0
    terms = rf.derive_terms(
        arch=arch, shape=shape, mesh_name=mesh_name, chips=mesh_chips(mesh),
        cost=cost, hlo_text=hlo, model_flops=rf.model_flops_for(cfg, cell),
        bytes_per_chip=float(bytes_per_chip), default_dynamic_trip=dyn_trip)
    rec = {"status": "ok", "lower_s": round(t_lower, 1),
           "compile_s": round(t_compile, 1),
           **({"grades_collective_curve": rf.grades_collective_curve(cfg)}
              if cell.kind == "train" else {}),
           "memory_analysis": {
               "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
               "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
               "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
               "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
           },
           **dataclasses.asdict(terms)}
    _write(out_dir, rec)
    if verbose:
        print(json.dumps({k: rec[k] for k in (
            "arch", "shape", "mesh", "status", "compute_s", "memory_s",
            "collective_s", "bottleneck", "useful_ratio", "roofline_frac")},
            indent=None))
        print("memory_analysis:", rec["memory_analysis"])
        print("cost_analysis flops=%.3e bytes=%.3e" % (terms.hlo_flops,
                                                       terms.hlo_bytes))
    return rec


def _write(out_dir: str, rec: Dict):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def run_all(out_dir: str, jobs: int, meshes, archs=None, shapes=None,
            skip_existing: bool = True):
    cells = []
    for arch in (archs or configs.ASSIGNED):
        for shape in (shapes or SHAPES):
            for mesh in meshes:
                name = f"{arch}__{shape}__{mesh}.json"
                if skip_existing and os.path.exists(os.path.join(out_dir, name)):
                    continue
                cells.append((arch, shape, mesh))
    procs = []
    results = {"ok": 0, "skip": 0, "fail": 0}
    idx = 0
    while idx < len(cells) or procs:
        while idx < len(cells) and len(procs) < jobs:
            arch, shape, mesh = cells[idx]
            idx += 1
            p = subprocess.Popen(
                [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                 "--shape", shape, "--mesh", mesh, "--out", out_dir],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
            procs.append((p, (arch, shape, mesh)))
        for p, cell in list(procs):
            if p.poll() is not None:
                procs.remove((p, cell))
                out = p.stdout.read()
                tag = "ok" if p.returncode == 0 else "fail"
                if p.returncode == 0 and '"status": "skip"' in out:
                    tag = "skip"
                results[tag] += 1
                print(f"[{tag}] {cell}  ({results})", flush=True)
                if tag == "fail":
                    print(out[-3000:], flush=True)
        time.sleep(0.5)
    print("DONE", results)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", choices=["opt", "baseline"], default="opt")
    args = ap.parse_args()
    if args.all:
        run_all(args.out, args.jobs, meshes=["single", "multi"],
                skip_existing=not args.force)
    else:
        rec = run_cell(args.arch, args.shape, args.mesh, args.out,
                       variant=args.variant)
        sys.exit(0 if rec["status"] in ("ok", "skip") else 1)


if __name__ == "__main__":
    main()
