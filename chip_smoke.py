#!/usr/bin/env python3
"""Chip smoke test: the trainer and the serve cell on a TPU, at the published
widths of qwen3-0.6b (28 layers, d 1024, 16/8 heads, hd 128, ff 3072, vocab
151936), with random weights from a seed.

    python chip_smoke.py              # one chip: train + serve phases
    python chip_smoke.py --chips 4    # four chips: data-parallel train only

Phases (one process; every failure raises, nothing is caught):

* train — ``Trainer`` with GradES on and ``kernels="auto"``, which must
  resolve to compiled Pallas kernels.  Three sync blocks of seeded synthetic
  batches, a whole-type freeze at the first monitored step and the Tier-1
  re-jit it triggers.  Reference: the same steps with ``kernels="jnp"``;
  losses, freeze decisions, each leaf's parameter change and the Eq.-1
  monitor norms must agree.
* serve — ``ServeEngine`` answers 8 requests (128-token prompts, 32 new
  tokens, 8 slots) through the flash prefill and the paged decode kernel.
  Reference: the same requests with ``attn_backend="jnp"``.  The paged
  decode kernel is also checked alone against its jnp reference at the
  head layouts of the other served configurations (hd 64 and 112).
* dp4 (``--chips 4`` only) — the train phase on a pure data-parallel
  ``("data",)`` mesh over four chips with the freeze-aware explicit reduce
  engaged.  Reference: the one-chip run of the same global batch.

The programs each phase runs (the trainer's sync block, the engine's
prefill and decode block) are also lowered alone to check that the Pallas
kernels are in them (no per-call fallback to jnp).  Earlier lines are informational
JSON; the last line is ``{"ok": true, "device": {...}}``.  Without a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.distributed import explicit_reduce_axes, use_mesh
from repro.kernels import compiled_kernels
from repro.kernels.dispatch import resolve_backend
from repro.launch.cache import enable_compile_cache
from repro.kernels.decode_attention import (paged_decode_attention,
                                            paged_decode_ref)
from repro.launch.mesh import make_dp_mesh, rules_for
from repro.models import model
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Request
from repro.train.loop import Trainer
from repro.train.step import make_multi_step

SEED = 0
#: matrix types given a tau no gradient change can stay under — they freeze
#: at the first monitored step on every backend; the rest have tau 0 and
#: never freeze.  Freeze decisions thus cannot hinge on rounding, and the
#: run still exercises the freeze gate, the Tier-1 re-jit and the packed
#: moments.
FREEZE_TYPES = ("layers/wq", "layers/wk", "layers/w_up")
#: |loss(kernels) - loss(reference)| bound.  Both runs keep bf16 activations;
#: the Pallas kernels accumulate attention and the optimizer update in f32 in
#: another order than XLA's jnp path.  bf16 carries 8 mantissa bits (relative
#: step 2**-8 ~ 4e-3), a CE loss near ln(151936) ~ 11.9 is a mean over 512
#: tokens whose rounding errors mostly cancel, and the few steps at lr 2e-5
#: move the weights by ~1e-4 — so 2e-2 is several times the spread expected.
LOSS_ATOL = 2e-2
#: ||dW(kernels) - dW(reference)|| / ||dW(reference)|| bound per parameter
#: leaf, dW = final - initial parameters.  AdamW's first steps move each
#: element by ~lr * sign(g), so elements whose gradient sits near zero may
#: step the other way under a different summation order.  Sound runs on a
#: v5e read at most 0.167 (the embedding; the other leaves 0.016-0.095), so
#: the bound is twice that.  A step that drops the update reads 1, an
#: uncorrelated one ~1.4.
DELTA_RTOL = 0.35
#: relative bound on each layer's Eq.-1 monitor norm (L1 of the change of
#: the gradient), which is scale-sensitive where AdamW's update is not.
#: Sound runs on a v5e read at most 1.1e-3.
NORM_RTOL = 5e-3
#: paged decode kernel vs its jnp reference: bf16 inputs and outputs, f32
#: softmax in both, outputs of O(1), so a few bf16 ulps (2**-7 in [1, 2))
DECODE_ATOL = 2e-2
#: (KV heads, query heads per KV head, head dim, page size) of the served
#: configurations whose head layout differs from qwen3-0.6b's
DECODE_LAYOUTS = {"hymba-1.5b": (5, 5, 64, 16),
                  "whisper-large-v3": (20, 1, 64, 16),
                  "kimi-k2": (8, 8, 112, 16)}
#: a served stream may leave the reference only at a near tie: at the first
#: divergent step, the token the kernels chose must score within this many
#: logits of the best token under a full jnp forward of the shared prefix.
#: That is two bf16 ulps at |logit| in [4, 8): random weights give logits of
#: O(1) and top-1/top-2 gaps of ~0.1 over a 151936-entry vocabulary, so ties
#: at bf16 resolution do occur.
LOGIT_ATOL = 0.0625


def require(ok, *context):
    """A phase check that also holds under ``python -O``."""
    if not ok:
        raise AssertionError(context)


def emit(**kv):
    print(json.dumps(kv), flush=True)


def peak_bytes() -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def train_config(**kw) -> TrainConfig:
    base = dict(seq_len=128, global_batch=4, steps=6, sync_interval=2,
                remat="full", kernels="auto", seed=SEED,
                grades=GradESConfig(enabled=True, alpha=0.5, tau=0.0,
                                    tau_overrides={t: 1e9
                                                   for t in FREEZE_TYPES}))
    base.update(kw)
    return TrainConfig(**base)


def initial_params(cfg, tcfg):
    """The parameters every run of ``tcfg`` starts from, on the host."""
    return jax.device_get(jax.jit(
        lambda: Trainer(cfg, tcfg).init_state().params)())


def run_train(cfg, tcfg, p0, tag: str):
    """One Trainer run from the parameters ``p0``; returns the per-step
    losses, the final freeze masks, each leaf's parameter change and the
    last Eq.-1 monitor norms."""
    trainer = Trainer(cfg, tcfg, repartition_interval=tcfg.sync_interval,
                      log_every=1)
    t0 = time.perf_counter()
    res = trainer.train()
    wall = time.perf_counter() - t0
    losses = [r["loss"] for r in res.history if "loss" in r]
    frozen = {k: np.asarray(v)
              for k, v in jax.device_get(res.state.grades.frozen).items()}
    norms = {k: np.asarray(v)
             for k, v in jax.device_get(res.state.grades.last_norm).items()}
    deltas = jax.tree.map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        jax.device_get(res.state.params), p0)
    require(res.steps_run == tcfg.steps, tag, res.steps_run, res.stop_reason)
    require(len(losses) == tcfg.steps, (tag, len(losses)))
    require(all(np.isfinite(losses)), (tag, losses))
    emit(phase=tag, wall_s=wall, losses=losses, recompiles=res.recompiles,
         stop=res.stop_reason, peak_bytes_in_use=peak_bytes())
    del res, trainer
    return losses, frozen, deltas, norms


def check_train_step_kernels(cfg, tcfg, tag: str):
    """Compile the trainer's first sync-block program alone and check that
    the flash pair and the fused GradES kernels are in it."""
    state = jax.eval_shape(Trainer(cfg, tcfg).init_state)
    spec = build_monitor_spec(state.params)
    K, B, S = tcfg.sync_interval, tcfg.global_batch, tcfg.seq_len
    block = {k: jax.ShapeDtypeStruct((K, B, S), jnp.int32)
             for k in ("tokens", "labels")}
    fn = jax.jit(make_multi_step(cfg, tcfg, spec,
                                 backend=resolve_backend(tcfg.kernels)),
                 donate_argnums=0)
    t0 = time.perf_counter()
    compiled = fn.lower(state, block).compile()
    hlo = compiled.as_text()
    found = compiled_kernels(hlo)
    mem = compiled.memory_analysis()
    emit(phase=tag, compile_s=time.perf_counter() - t0,
         tpu_custom_calls=dict(found),
         all_reduces=hlo.count(" all-reduce("),
         argument_bytes=mem.argument_size_in_bytes,
         temp_bytes=mem.temp_size_in_bytes)
    n_monitored = sum(len(paths) for paths, _ in spec.groups.values())
    for name in ("flash_fwd", "flash_dq", "flash_dkv"):
        require(found[name] >= 1, (tag, name, dict(found)))
    for name in ("grades_norm", "masked_adamw"):
        require(found[name] == n_monitored, (tag, name, dict(found)))


def compare_train(a, b, tag: str):
    (la, fa, da, na), (lb, fb, db, nb) = a, b
    diff = float(np.max(np.abs(np.asarray(la) - np.asarray(lb))))
    delta_rel, delta_norm = {}, {}
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(da),
                            jax.tree.leaves(db)):
        key = jax.tree_util.keystr(path)
        delta_norm[key] = float(np.linalg.norm(y))
        delta_rel[key] = float(np.linalg.norm(x - y)) / max(delta_norm[key],
                                                           1e-30)
    norm_rel = {k: float(np.max(np.abs(na[k] - nb[k])
                                / np.maximum(np.abs(nb[k]), 1e-30)))
                for k in nb}
    emit(phase=tag, max_abs_loss_diff=diff, loss_atol=LOSS_ATOL,
         frozen_types=sorted(k for k in fa if fa[k].all()),
         delta_rel=delta_rel, delta_norm=delta_norm, delta_rtol=DELTA_RTOL,
         norm_rel=norm_rel, norm_rtol=NORM_RTOL)
    require(diff <= LOSS_ATOL, (tag, diff, la, lb))
    require(fa.keys() == fb.keys())
    for k in fa:
        require(np.array_equal(fa[k], fb[k]), (tag, k, fa[k], fb[k]))
        require(bool(fa[k].all()) == (k in FREEZE_TYPES), (tag, k, fa[k]))
        require(fa[k].any() == fa[k].all(), (tag, k, fa[k]))
    for k, r in delta_rel.items():     # every leaf trains before any freeze
        require(delta_norm[k] > 0 and r <= DELTA_RTOL, (tag, k, r))
    for k, r in norm_rel.items():
        require(np.isfinite(nb[k]).all() and r <= NORM_RTOL, (tag, k, r))


def train_phase(cfg):
    tcfg = train_config()
    backend = resolve_backend(tcfg.kernels)
    require(backend.use_pallas and not backend.interpret, backend)
    check_train_step_kernels(cfg, tcfg, "train/kernels")
    p0 = initial_params(cfg, tcfg)
    got = run_train(cfg, tcfg, p0, "train/pallas")
    ref = run_train(cfg, dataclasses.replace(tcfg, kernels="jnp"), p0,
                    "train/jnp")
    compare_train(got, ref, "train/compare")


def dp_phase(cfg, chips: int):
    tcfg = train_config()
    p0 = initial_params(cfg, tcfg)
    mesh = make_dp_mesh(chips)
    tag = f"dp{chips}"
    with use_mesh(mesh, rules_for(mesh)):
        require(explicit_reduce_axes(mesh, tcfg) == ("data",), mesh)
        backend = resolve_backend(tcfg.kernels)
        require(backend.use_pallas and not backend.interpret, backend)
        check_train_step_kernels(cfg, tcfg, f"{tag}/kernels")
        got = run_train(cfg, tcfg, p0, f"{tag}/pallas")
    ref = run_train(cfg, tcfg, p0, f"{tag}/one_chip")
    compare_train(got, ref, f"{tag}/compare")


def serve_requests(cfg, n=8, prompt_len=128, max_new=32):
    rng = np.random.default_rng(SEED)
    return [Request(rid=i, prompt=tuple(int(t) for t in
                                        rng.integers(0, cfg.vocab,
                                                     prompt_len)),
                    max_new=max_new, arrival_tick=0) for i in range(n)]


def run_serve(params, cfg, reqs, tag: str):
    engine = ServeEngine(params, cfg, max_slots=8, max_len=160)
    t0 = time.perf_counter()
    streams, metrics = engine.run(reqs, install_signals=False)
    emit(phase=tag, wall_s=time.perf_counter() - t0,
         completed=metrics["completed"], tokens=metrics["total_new_tokens"],
         peak_bytes_in_use=peak_bytes())
    require(metrics["completed"] == len(reqs), (tag, metrics["statuses"]))
    for r in reqs:
        require(len(streams[r.rid]) == r.max_new, (tag, r.rid))
    return streams, engine


def check_serve_kernels(engine, prompt_len: int, tag: str):
    """Compile the engine's own prefill and decode-block programs alone and
    check that the flash and paged-decode kernels are in them."""
    B = engine.max_slots
    prefill = engine._prefill.lower(
        engine.params,
        jax.ShapeDtypeStruct((B, prompt_len), jnp.int32)).compile()
    decode = engine._block.lower(
        engine.params, engine.pool, engine._tokens_dev, engine._active_dev,
        jnp.ones((B,), jnp.float32)).compile()
    found_p = compiled_kernels(prefill.as_text())
    found_d = compiled_kernels(decode.as_text())
    emit(phase=tag, prefill_tpu_custom_calls=dict(found_p),
         decode_tpu_custom_calls=dict(found_d))
    require(found_p["flash_fwd"] >= 1, (tag, dict(found_p)))
    require(found_d["paged_decode"] >= 1, (tag, dict(found_d)))


def check_decode_layouts(tag: str):
    """The paged decode kernel alone against its jnp reference at the head
    layouts of the other served configurations: 8 slots of 160 tokens in a
    shuffled pool, ragged valid counts."""
    worst = {}
    for name, (KV, G, hd, ps) in DECODE_LAYOUTS.items():
        B, P = 8, 160 // ps
        N = 1 + B * P
        ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
        q = jax.random.normal(ks[0], (B, 1, KV, G, hd), jnp.bfloat16)
        kp = jax.random.normal(ks[1], (N, ps, KV, hd), jnp.bfloat16)
        vp = jax.random.normal(ks[2], (N, ps, KV, hd), jnp.bfloat16)
        rng = np.random.default_rng(SEED)
        table = jnp.asarray(rng.permutation(np.arange(1, N)).reshape(B, P),
                            jnp.int32)
        vc = jnp.asarray(rng.integers(1, P * ps + 1, B), jnp.int32)
        fn = jax.jit(lambda *a: paged_decode_attention(*a))
        found = compiled_kernels(
            fn.lower(q, kp, vp, table, vc).compile().as_text())
        require(found["paged_decode"] == 1, (tag, name, dict(found)))
        out = np.asarray(fn(q, kp, vp, table, vc), np.float32)
        ref = np.asarray(paged_decode_ref(q, kp, vp, table, vc), np.float32)
        worst[name] = float(np.max(np.abs(out - ref)))
    emit(phase=tag, max_abs_diff=worst, atol=DECODE_ATOL)
    for name, d in worst.items():
        require(d <= DECODE_ATOL, (tag, name, d))


def serve_phase(cfg):
    params = model.init_params(jax.random.PRNGKey(SEED), cfg)
    reqs = serve_requests(cfg)
    got, engine = run_serve(params, cfg, reqs, "serve/pallas")
    check_serve_kernels(engine, len(reqs[0].prompt), "serve/kernels")
    del engine
    ref_cfg = dataclasses.replace(cfg, attn_backend="jnp")
    ref, engine = run_serve(params, ref_cfg, reqs, "serve/jnp")
    del engine
    ref_logits = jax.jit(lambda p, t: model.forward(
        p, ref_cfg, {"tokens": t})[0][0, -1].astype(jnp.float32))
    gaps = {}
    for r in reqs:
        a, b = got[r.rid], ref[r.rid]
        t = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if t is None:
            continue
        seq = jnp.asarray([list(r.prompt) + list(b[:t])], jnp.int32)
        logits = np.asarray(ref_logits(params, seq))
        gaps[r.rid] = (t, float(logits.max() - logits[a[t]]))
    emit(phase="serve/compare", identical=len(reqs) - len(gaps),
         divergent={k: {"step": t, "logit_gap": g}
                    for k, (t, g) in gaps.items()},
         logit_atol=LOGIT_ATOL)
    for rid, (t, g) in gaps.items():
        require(g <= LOGIT_ATOL, (rid, t, g))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = data-parallel train phase over four chips "
                         "against the one-chip run (no other phase)")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX platform "
                 f"{devices[0].platform!r}); nothing was run")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, found {len(devices)}")
    emit(compile_cache=enable_compile_cache())
    cfg = configs.get("qwen3-0.6b")
    if args.chips > 1:
        dp_phase(cfg, args.chips)
    else:
        train_phase(cfg)
        serve_phase(cfg)
        check_decode_layouts("serve/decode_layouts")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": args.chips}}), flush=True)


if __name__ == "__main__":
    main()
