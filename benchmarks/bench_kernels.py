"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp oracle, plus the
analytic HBM-traffic advantage the kernels were written for (the interpret-mode
wall time is NOT TPU time; the traffic model is the transferable number).

The fused-step section compares one GradES step over a stacked parameter —
monitor norm (Eq. 1) + frozen-gated optimizer update — through the kernel
dispatch path vs the jnp reference, sweeping the frozen fraction.  Off-TPU the
measured column is interpret-mode emulation (flagged as such); the modeled
column is the HBM roofline both paths would hit on hardware:

* jnp monitor: ~4 passes over the gradient bytes (sub, abs-reduce, prev copy);
  fused ``grades_norm``: 2 reads + 1 write for live layers — frozen layers
  cost one flag load (the freeze gate; prev write-back elided under aliasing).
* jnp update: XLA's ``where`` streams p/g/m/v and rewrites p/m/v for every
  layer (7 passes); fused ``masked_adamw`` pays that only for live layers —
  frozen layers cost one SMEM flag load (no-op writes under aliasing).

The segmented-step section sweeps the Tier-1.5 segment plan (DESIGN.md §2):
one full jitted train step of a reduced config, monolithic scan vs the
chain-of-segment-scans plan, at per-layer frozen fractions
{0, 0.25, 0.5, 0.75} × ``segment_max`` ∈ {1, 4, 8} — modeled dW FLOPs from the
§8 roofline term next to measured step time (the dW elimination is
backend-independent: it is real XLA compute dropped even on CPU).

The attention section (§3b) sweeps one fwd+bwd attention call — the flash
kernel pair vs the blockwise-jnp schedule — over GQA on/off × 4k/32k with the
§8 HBM-bytes roofline accounting: flash streams only the q/k/v/o slabs while
the jnp path also round-trips the touched (S×T) score area through HBM.

Results land in ``artifacts/bench/kernels.json`` and a repo-level
``BENCH_kernels.json`` so the perf trajectory is tracked in-tree.

This benchmark is not run on the chip and none of its times is a device
time: the multi-device sweeps run in child processes pinned to the CPU
(``JAX_PLATFORMS=cpu`` with forced host devices), so they never contend for
an accelerator the parent may hold.  The on-chip benchmark replaces it.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

from benchmarks.common import out_path
from repro.kernels import ops, ref

#: HBM bandwidth used for the roofline model (TPU v4-class, bytes/s).
HBM_BW = 1.2e12

REPO_BENCH = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*args)
        jax.tree.leaves(r)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def _fused_step_rows(reps=5):
    """One GradES step (monitor + masked update) for a stacked (L, M, N) leaf,
    fused dispatch path vs jnp reference, at frozen fractions 0 / 0.5 / 1."""
    L, M, N = 8, 256, 1024
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    p = jax.random.normal(ks[0], (L, M, N))
    g = jax.random.normal(ks[1], (L, M, N))
    m = jax.random.normal(ks[2], (L, M, N)) * 0.1
    v = jax.random.uniform(ks[3], (L, M, N)) * 0.01
    prev = jax.random.normal(ks[4], (L, M, N))
    kw = dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.01)
    on_tpu = jax.default_backend() == "tpu"

    @jax.jit
    def fused_step(p, g, m, v, prev, flags, lr, count):
        norm, new_prev = ops.grades_norm(g, prev, flags, interpret=not on_tpu)
        pn, mn, vn = ops.masked_adamw(p, g, m, v, flags, lr, count,
                                      interpret=not on_tpu, **kw)
        return pn, mn, vn, norm, new_prev

    @jax.jit
    def jnp_step(p, g, m, v, prev, flags, lr, count):
        norm = jnp.sum(jnp.abs(g - prev), axis=(1, 2))
        pn, mn, vn = ref.masked_adamw_ref(p, g, m, v, flags, lr=lr,
                                          count=count, **kw)
        return pn, mn, vn, norm, g

    bytes_leaf = p.size * p.dtype.itemsize
    rows = []
    for frac in (0.0, 0.5, 1.0):
        flags = jnp.arange(L) < int(frac * L)
        args = (p, g, m, v, prev, flags, 1e-3, 5.0)
        fused_us = _time(lambda *a: fused_step(*a), *args, reps=reps)
        jnp_us = _time(lambda *a: jnp_step(*a), *args, reps=reps)
        # HBM roofline: both the freeze-gated monitor (3 passes) and the
        # masked update (7 passes) stream live layers only — frozen layers
        # cost the (L,) int32 flag loads; the jnp paths stream every layer.
        fused_bytes = bytes_leaf * (3 + 7) * (1.0 - frac) + 2 * L * 4
        jnp_bytes = bytes_leaf * (4 + 7)
        fused_model = fused_bytes / HBM_BW * 1e6
        jnp_model = jnp_bytes / HBM_BW * 1e6
        rows.append({
            "name": f"fused_step_vs_jnp/frozen_{frac}",
            "frozen_frac": frac,
            "fused_us": round(fused_us if on_tpu else fused_model, 3),
            "jnp_us": round(jnp_us if on_tpu else jnp_model, 3),
            "speedup": round((jnp_us / fused_us) if on_tpu
                             else (jnp_model / fused_model), 3),
            "modeled_fused_us": round(fused_model, 3),
            "modeled_jnp_us": round(jnp_model, 3),
            "measured_fused_us": round(fused_us, 1),
            "measured_jnp_us": round(jnp_us, 1),
            "measured_is_emulation": not on_tpu,
            "shape": [L, M, N],
            "hbm_bw_model": HBM_BW,
        })
    return rows


def _attn_hbm_bytes(B, S, T, KV, G, hd, itemsize, causal):
    """Roofline HBM-bytes model (§8) for one attention fwd+bwd, flash kernels
    vs the blockwise jnp schedule.

    Flash (kernels/flash_attention.py) keeps every score tile in VMEM: HBM
    traffic is the q/k/v/o slabs only — fwd reads q+k+v and writes o; bwd runs
    the delta pass (read o, do), the dq pass (read q,k,v,do; write dq) and the
    dk/dv pass (read q,k,v,do; write dk,dv).  The blockwise jnp path streams
    the same slabs but ALSO round-trips each (q_chunk × kv_chunk) score block
    through HBM (XLA materializes s/p between the einsum and softmax ops):
    ~2 passes over the touched (S×T) score area forward, ~4 backward (autodiff
    rematerializes s and streams dp/ds).  Causality halves the touched area.
    """
    q_b = B * S * KV * G * hd * itemsize
    kv_b = B * T * KV * hd * itemsize
    frac = 0.5 if causal else 1.0
    score_b = B * KV * G * S * T * 4 * frac  # f32 score blocks
    flash_fwd = 3 * q_b + 2 * kv_b            # r(q) + r(k,v) + w(o) (lse ~ 0)
    flash_bwd = (2 * q_b                      # delta: r(o), r(do)
                 + 3 * q_b + 2 * kv_b         # dq:    r(q,do) w(dq) + r(k,v)
                 + 2 * q_b + 4 * kv_b)        # dk/dv: r(q,do) + r/w(k,v,dk,dv)
    jnp_fwd = 3 * q_b + 2 * kv_b + 2 * score_b
    jnp_bwd = 5 * q_b + 4 * kv_b + 4 * score_b
    return flash_fwd + flash_bwd, jnp_fwd + jnp_bwd


def _attention_rows(reps=3):
    """Fwd+bwd attention sweep: flash (Pallas) vs blockwise-jnp, GQA on/off,
    4k/32k.  Off-TPU the headline numbers are the HBM roofline model (the
    transferable quantity); a small anchor shape is measured in interpret
    mode for parity/trend only."""
    from repro.kernels.flash_attention import flash_attention
    from repro.models.attention import blockwise_attention

    on_tpu = jax.default_backend() == "tpu"
    itemsize = 2  # bf16 activations in the production step
    rows = []
    for gqa, (KV, G) in (("gqa_off", (8, 1)), ("gqa_on", (2, 4))):
        for S in (4096, 32768):
            B, hd = 1, 128
            flash_b, jnp_b = _attn_hbm_bytes(B, S, S, KV, G, hd, itemsize,
                                             causal=True)
            row = {
                "name": f"attention_fwd_bwd/{S // 1024}k/{gqa}",
                "shape": {"B": B, "S": S, "KV": KV, "G": G, "hd": hd},
                "hbm_bytes_flash": flash_b,
                "hbm_bytes_jnp": jnp_b,
                "hbm_reduction": round(jnp_b / flash_b, 2),
                "modeled_flash_us": round(flash_b / HBM_BW * 1e6, 1),
                "modeled_jnp_us": round(jnp_b / HBM_BW * 1e6, 1),
                "hbm_bw_model": HBM_BW,
            }
            if on_tpu:  # real kernels at real shapes; off-TPU see the anchor
                row.update(_measure_attn(flash_attention, blockwise_attention,
                                         B, S, KV, G, hd, reps, interpret=False))
            rows.append(row)

    # interpret-mode anchor: small shape, same code paths, emulation-only.
    if not on_tpu:
        anchor = _measure_attn(flash_attention, blockwise_attention,
                               1, 512, 2, 2, 64, reps, interpret=True)
        rows.append({"name": "attention_fwd_bwd/anchor_512_emulation",
                     "shape": {"B": 1, "S": 512, "KV": 2, "G": 2, "hd": 64},
                     "measured_is_emulation": True, **anchor})
    return rows


def _measure_attn(flash_fn, blockwise_fn, B, S, KV, G, hd, reps, *, interpret):
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)

    def fwd_bwd(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32))
        return jax.jit(jax.grad(loss, (0, 1, 2)))

    flash = fwd_bwd(lambda q, k, v: flash_fn(q, k, v, causal=True,
                                             interpret=interpret))
    ref = fwd_bwd(lambda q, k, v: blockwise_fn(q, k, v, causal=True,
                                               q_chunk=min(S, 256),
                                               kv_chunk=min(S, 256)))
    return {
        "measured_flash_us": round(_time(lambda *a: flash(*a), q, k, v,
                                         reps=reps), 1),
        "measured_jnp_us": round(_time(lambda *a: ref(*a), q, k, v,
                                       reps=reps), 1),
    }


def _segment_rows(reps=3):
    """Tier-1.5 sweep: a full jitted train step, monolithic layer scan vs the
    segment plan, at per-layer frozen fractions {0, .25, .5, .75} ×
    ``segment_max`` ∈ {1, 4, 8}.  ``segment_max=1`` IS the monolithic scan
    (single segment, whole-type-only signature), so its row doubles as the
    baseline.  The modeled column is the §8 dW term; the measured step time
    is real XLA compute on any backend (stop_gradient drops the dW einsums at
    trace time, not in a TPU-only pass)."""
    import dataclasses as _dc

    import numpy as np

    import repro.configs as configs
    from repro.config import GradESConfig, TrainConfig
    from repro.core.grades import build_monitor_spec
    from repro.core.partition import plan_skipped_params, segment_plan
    from repro.data.pipeline import make_batches
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    cfg = _dc.replace(configs.reduced("qwen3-0.6b"), n_layers=8)
    tcfg = TrainConfig(seq_len=64, global_batch=4, steps=100, lr=1e-3,
                       grades=GradESConfig(enabled=True, tau=0.0, alpha=0.5,
                                           normalize=True))
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    spec = build_monitor_spec(state.params)
    batch = next(iter(make_batches(cfg, tcfg, steps=1)))
    tokens = tcfg.global_batch * tcfg.seq_len
    L = cfg.n_layers
    pool = sum(int(np.prod(state.params["layers"][k].shape))
               for k in state.params["layers"] if not k.endswith("norm"))

    rows = []
    for frac in (0.0, 0.25, 0.5, 0.75):
        n_frozen = int(frac * L)
        frozen_host = {n: np.arange(L) < n_frozen for n in spec.groups}
        for seg_max in (1, 4, 8):
            plan = segment_plan(frozen_host, spec, L, seg_max)
            step = jax.jit(make_train_step(cfg, tcfg, spec, plan=plan))
            skipped = plan_skipped_params(plan, state.params["layers"], L)

            def run_step(s, b):
                new_s, m = step(s, b)
                return (m["loss"],)  # keep donation-free: state reused

            us = _time(lambda *a: run_step(*a), state, batch, reps=reps)
            rows.append({
                "name": f"segmented_step/frozen_{frac}/segmax_{seg_max}",
                "frozen_frac": frac,
                "segment_max": seg_max,
                "segments": [[lo, hi, sorted(sig)]
                             for lo, hi, sig in plan.segments],
                "dw_skip_params": int(skipped),
                "modeled_dw_flops": 2.0 * (pool - skipped) * tokens,
                "modeled_dw_skip_frac": round(skipped / pool, 4),
                "measured_step_us": round(us, 1),
            })
    return rows


def _loop_overhead_rows():
    """Host-loop overhead sweep (DESIGN.md §4): steady-state per-step wall
    time for ``sync_interval ∈ {1, 8, 32}`` × prefetch on/off on a tiny dense
    model whose per-step compute is small enough that the per-step Python
    dispatch + device_get round-trip is visible.  The device floor is the
    compiled 32-step block timed back-to-back on pre-staged device blocks (no
    controller, no metric drain) — ``host_overhead_us_per_step`` is the
    steady-state p50 minus that floor, and must shrink as the host wakes only
    once per K steps."""
    import dataclasses

    from repro.config import GradESConfig, ModelConfig, TrainConfig
    from repro.core.grades import build_monitor_spec
    from repro.data.pipeline import make_batches, stack_batches
    from repro.train.loop import Trainer
    from repro.train.state import init_train_state
    from repro.train.step import make_multi_step

    cfg = ModelConfig(name="bench-tiny", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    steps = 320  # 10 blocks at K=32 -> a stable p50 window
    base = TrainConfig(
        seq_len=8, global_batch=4, steps=steps, lr=1e-3,
        # tau=0 keeps every step's compute identical across the sweep (no
        # freezing, no Tier-1 sync) — differences are pure host overhead.
        grades=GradESConfig(enabled=True, tau=0.0, alpha=0.5, normalize=True,
                            static_repartition=False))

    # --- device floor: compiled 32-step scan, pre-staged blocks, hot ---
    # Per-block times with the min estimator (the block's pure execution,
    # free of scheduler noise); measured after a warmup so every steady_us
    # row sits above it.
    state = init_train_state(jax.random.PRNGKey(0), cfg, base)
    spec = build_monitor_spec(state.params)
    multi = jax.jit(make_multi_step(cfg, base, spec), donate_argnums=0)
    blocks = [jax.device_put(stack_batches(
        list(make_batches(cfg, base, steps=32, start_step=i * 32))))
        for i in range(9)]
    state, m = multi(state, blocks[0])
    jax.block_until_ready(m)  # compile
    state, m = multi(state, blocks[1])
    jax.block_until_ready(m)  # warm
    per_block = []
    for b in blocks[2:]:
        t0 = time.perf_counter()
        state, m = multi(state, b)
        jax.block_until_ready((state, m))
        per_block.append(time.perf_counter() - t0)
    floor_us = min(per_block) / 32 * 1e6

    rows = []
    for K in (1, 8, 32):
        for depth in (2, 0):
            tcfg = dataclasses.replace(base, sync_interval=K,
                                       prefetch_depth=depth)
            t0 = time.perf_counter()
            res = Trainer(cfg, tcfg, log_every=steps).train()
            wall_us = (time.perf_counter() - t0) / steps * 1e6
            # steady-state per-step p50 from the watchdog window (block
            # completion deltas; excludes the compile-polluted first block)
            p50_us = res.history[-1]["dt_p50"] * 1e6
            rows.append({
                "name": f"loop_overhead/sync_{K}/"
                        f"prefetch_{'on' if depth else 'off'}",
                "sync_interval": K,
                "prefetch": bool(depth),
                "steps": steps,
                "steps_per_sec": round(1e6 / p50_us, 1),
                "wall_us_per_step": round(wall_us, 1),
                "steady_us_per_step": round(p50_us, 1),
                "device_floor_us_per_step": round(floor_us, 1),
                "host_overhead_us_per_step": round(max(p50_us - floor_us,
                                                       0.0), 1),
            })
    return rows


def _guard_overhead_rows():
    """Numerics-guard cost (DESIGN.md §4): the all-finite sentinel is two
    ``jnp.isfinite`` ops on scalars the step already computes (loss,
    grad_norm) plus one extra ``(K,)`` float in the per-block metrics bundle —
    no extra device sync, no extra HBM pass over parameters.  Measured like
    the loop-overhead device floor: the compiled K-step block on pre-staged
    device blocks, min estimator, guard on vs off.  Budget: ≤1% of the fused
    block time."""
    import dataclasses

    from repro.config import GradESConfig, ModelConfig, TrainConfig
    from repro.core.grades import build_monitor_spec
    from repro.data.pipeline import make_batches, stack_batches
    from repro.train.state import init_train_state
    from repro.train.step import make_multi_step

    import statistics

    cfg = ModelConfig(name="bench-tiny", family="dense", n_layers=2,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64)
    K, n_blocks = 32, 32
    base = TrainConfig(
        seq_len=8, global_batch=4, steps=K * n_blocks, lr=1e-3,
        sync_interval=K,
        # tau=0: no freezing, every step runs the full update — the guard
        # delta is isolated from Tier-1/Tier-2 path changes.
        grades=GradESConfig(enabled=True, tau=0.0, alpha=0.5, normalize=True,
                            static_repartition=False))
    blocks = [jax.device_put(stack_batches(
        list(make_batches(cfg, base, steps=K, start_step=i * K))))
        for i in range(n_blocks)]

    def compiled(tcfg):
        state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
        spec = build_monitor_spec(state.params)
        fn = jax.jit(make_multi_step(cfg, tcfg, spec), donate_argnums=0)
        ca = fn.lower(state, blocks[0]).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one dict per device
            ca = ca[0]
        return state, fn, ca["flops"]

    on_state, on_fn, on_flops = compiled(base)
    off_state, off_fn, off_flops = compiled(
        dataclasses.replace(base, numerics_guard=False))
    for b in blocks[:2]:  # compile + warm both programs
        on_state, m = on_fn(on_state, b)
        jax.block_until_ready(m)
        off_state, m = off_fn(off_state, b)
        jax.block_until_ready(m)
    # Same data block through both programs back-to-back (separate donated
    # states), median of the paired per-block deltas: slow host-load drift
    # cancels within a pair, and the median rejects scheduler outliers — a
    # sequential A/B at this scale is pure noise.  The XLA cost-analysis
    # FLOP delta is the deterministic modeled check alongside.
    on_t, off_t = [], []
    for b in blocks[2:]:
        t0 = time.perf_counter()
        off_state, m = off_fn(off_state, b)
        jax.block_until_ready((off_state, m))
        t1 = time.perf_counter()
        on_state, m = on_fn(on_state, b)
        jax.block_until_ready((on_state, m))
        off_t.append(t1 - t0)
        on_t.append(time.perf_counter() - t1)
    deltas = [a - b for a, b in zip(on_t, off_t)]
    off_us = statistics.median(off_t) / K * 1e6
    delta_us = statistics.median(deltas) / K * 1e6
    q1, _, q3 = statistics.quantiles(deltas, n=4)
    noise_us = (q3 - q1) / 2 / K * 1e6  # half-IQR of the paired deltas
    overhead_pct = delta_us / off_us * 100
    noise_pct = noise_us / off_us * 100
    modeled_pct = (on_flops - off_flops) / off_flops * 100
    # Off-TPU the wall-clock delta is noise-bound (a ~0.0001% effect under a
    # few-% scheduler floor), so — as with the roofline columns elsewhere in
    # this file — the deterministic compiled-program FLOP delta is the budget
    # check and the measurement must merely be indistinguishable from noise.
    measured_ok = overhead_pct <= max(1.0, noise_pct)
    return [{
        "name": "numerics_guard/fused_block",
        "sync_interval": K,
        "guard_off_us_per_step": round(off_us, 2),
        "guard_delta_us_per_step": round(delta_us, 3),
        "overhead_pct": round(overhead_pct, 2),
        "noise_floor_pct": round(noise_pct, 2),
        "measured_is_noise_bound": bool(abs(overhead_pct) <= noise_pct),
        "modeled_flops_overhead_pct": round(modeled_pct, 4),
        "guard_on_flops": on_flops,
        "guard_off_flops": off_flops,
        "budget_pct": 1.0,
        "within_budget": bool(modeled_pct <= 1.0 and measured_ok),
    }]


#: subprocess body for the sharded sweep: the shard-mapped fused step vs the
#: jnp reference on a host (2 data, 4 model) mesh of 8 placeholder CPU
#: devices (the main bench process keeps its single-device view).
_SHARDED_BENCH = """
import json, time
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.config import TrainConfig
from repro.distributed.sharding import make_mesh
from repro.kernels import dispatch, ref

HBM_BW = %(hbm_bw)r
mesh = make_mesh((2, 4), ("data", "model"))
n_dev = mesh.devices.size
backend = dispatch.KernelBackend("pallas", interpret=True, mesh=mesh,
                                 forced=True)
tcfg = TrainConfig(optimizer="adamw", lr=1e-3, weight_decay=0.01,
                   b1=0.9, b2=0.95, eps=1e-8)
pspec = P(None, "data", "model")
L, M, N = 8, 256, 1024
ks = jax.random.split(jax.random.PRNGKey(7), 5)
sh = NamedSharding(mesh, pspec)
p, g, m, v, prev = (jax.device_put(jax.random.normal(k, (L, M, N)), sh)
                    for k in ks)

@jax.jit
def fused_step(p, g, m, v, prev, flags, lr, count):
    norm, new_prev = dispatch.fused_grades_norm(g, prev, 1, backend, pspec)
    pn, mn, vn = dispatch.fused_masked_update(p, g, m, v, flags, lr, count,
                                              tcfg, backend, pspec)
    return pn, mn, vn, norm, new_prev

@jax.jit
def jnp_step(p, g, m, v, prev, flags, lr, count):
    norm = jnp.sum(jnp.abs(g - prev), axis=(1, 2))
    pn, mn, vn = ref.masked_adamw_ref(p, g, m, v, flags, lr=lr, count=count,
                                      b1=0.9, b2=0.95, eps=1e-8,
                                      weight_decay=0.01)
    return pn, mn, vn, norm, g

def timed(fn, args, reps=3):
    jax.tree.leaves(fn(*args))[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.tree.leaves(fn(*args))[0].block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6

bytes_leaf = p.size * p.dtype.itemsize
rows = []
for frac in (0.0, 0.5, 1.0):
    flags = jnp.arange(L) < int(frac * L)
    args = (p, g, m, v, prev, flags, 1e-3, 5.0)
    fused_us = timed(fused_step, args)
    jnp_us = timed(jnp_step, args)
    # per-device HBM roofline: each of the n_dev shards streams 1/n_dev of the
    # leaf bytes in parallel; pass counts as in the single-device model.
    fused_model = bytes_leaf * (3 + 7 * (1.0 - frac)) / n_dev / HBM_BW * 1e6
    jnp_model = bytes_leaf * (4 + 7) / n_dev / HBM_BW * 1e6
    rows.append({
        "name": "sharded_fused_step_vs_jnp/frozen_%%s" %% frac,
        "frozen_frac": frac,
        "mesh": [2, 4],
        "fused_us": round(fused_model, 3),
        "jnp_us": round(jnp_model, 3),
        "speedup": round(jnp_model / fused_model, 3),
        "modeled_fused_us": round(fused_model, 3),
        "modeled_jnp_us": round(jnp_model, 3),
        "measured_fused_us": round(fused_us, 1),
        "measured_jnp_us": round(jnp_us, 1),
        "measured_is_emulation": True,
        "shape": [L, M, N],
        "hbm_bw_model": HBM_BW,
    })
print("JSON_ROWS " + json.dumps(rows))
"""


def _sharded_step_rows():
    """Host-8-device shard-mapped sweep, run in a subprocess so this process
    keeps its single-device view (same pattern as tests/test_distributed.py).
    The child is pinned to the CPU and never runs on the chip; this sweep
    tracks the shard_map dispatch overhead/parity trend on the CPU
    emulation."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=src)
    code = _SHARDED_BENCH % {"hbm_bw": HBM_BW}
    try:
        out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                             text=True, timeout=900, env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-500:])
        return json.loads(out.stdout.split("JSON_ROWS", 1)[1])
    except Exception as e:  # keep the rest of the bench usable anywhere
        return [{"name": "sharded_fused_step_vs_jnp/unavailable",
                 "note": str(e)[:500]}]


#: subprocess body for the freeze-aware reduce sweep: the explicit per-leaf
#: DP gradient reduce on a host 8-device ("data",) mesh, at frozen fractions
#: {0, .25, .5, .75} — measured wall time + measured HLO collective bytes
#: under the boundary ReducePlan, bit-identity vs the full-tree reduce, and
#: the modeled int8 wire bytes for the surviving leaves.
_REDUCE_BENCH = """
import json, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.config import GradESConfig, ModelConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.core.partition import (fully_frozen_types, gradient_reduce_plan,
                                  plan_row_masks, segment_plan,
                                  trainable_mask)
from repro.distributed import (compress_with_feedback, make_mesh,
                               reduce_gradients, reduce_plan_bytes)
from repro.launch.roofline import analyze_hlo
from repro.optim.optimizer import align_packed_tree
from repro.train.state import init_train_state

# Big enough that the reduce payload (~170 MB of layer grads) dominates the
# per-call dispatch overhead on the host-device emulation — at 40 MB the
# smallest sweep step (one type of seven dropped) sat inside the run-to-run
# scheduling noise.
cfg = ModelConfig(name="bench-reduce", family="dense", n_layers=4,
                  d_model=1024, n_heads=8, n_kv_heads=8, d_ff=2048, vocab=256)
tcfg = TrainConfig(seq_len=8, global_batch=8, steps=8, lr=1e-3,
                   grades=GradESConfig(enabled=True, tau=0.0, alpha=0.5,
                                       normalize=True))
params = init_train_state(jax.random.PRNGKey(0), cfg, tcfg).params
spec = build_monitor_spec(params)
L = cfg.n_layers
mesh = make_mesh((8,), ("data",))

def timed(fn, *args, reps=10):
    # min over many reps: CPU-emulated collectives jitter ~10% run-to-run on
    # a shared box, and the sweep's monotonicity check needs the floor, not
    # the mean.
    for _ in range(2):
        jax.tree.leaves(fn(*args))[0].block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.tree.leaves(fn(*args))[0].block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6

key = jax.random.PRNGKey(1)
leaves, treedef = jax.tree_util.tree_flatten(params)
ks = jax.random.split(key, len(leaves))
raw = jax.tree_util.tree_unflatten(
    treedef, [jax.random.normal(k, l.shape, jnp.float32)
              for k, l in zip(ks, leaves)])

names = sorted(spec.groups)
rows = []
timers = []
for mode in ("tier1_drop", "rowsliced"):
  for frac in (0.0, 0.25, 0.5, 0.75):
    if mode == "tier1_drop":
        # Tier-1 whole-type freezing: frac of the monitored types fully
        # frozen -> their leaves DROP from the reduce outright (the headline
        # monotone sweep: savings with zero stitch overhead).
        k = int(frac * len(names))
        frozen_host = {n: np.full(L, i < k)
                       for i, n in enumerate(names)}
    else:
        # Tier-1.5 per-layer freezing: frac of each type's layers frozen ->
        # row-sliced reduce entries (live ranges pmean'd, frozen gap rows
        # written as zeros).
        frozen_host = {n: np.arange(L) < int(frac * L) for n in spec.groups}
    static = fully_frozen_types(frozen_host)
    plan = segment_plan(frozen_host, spec, L, 8)
    rmasks = plan_row_masks(plan, spec, frozen_host)
    rplan = gradient_reduce_plan(spec, static, plan, L)
    trainable = trainable_mask(params, spec, static, rmasks)

    # grads exactly as the step produces them: zero on frozen leaves/rows
    # (stop_gradient upstream), live elsewhere.
    def zero_frozen(g, t):
        if isinstance(t, np.ndarray):
            m = jnp.asarray(t, g.dtype).reshape(
                t.shape + (1,) * (g.ndim - t.ndim))
            return g * m
        return g if t else jnp.zeros_like(g)

    grads = jax.tree.map(zero_frozen, raw, trainable)

    def reduce_with(rp):
        return jax.jit(jax.shard_map(
            lambda g: reduce_gradients(g, ("data",), rp), mesh=mesh,
            in_specs=(P(),), out_specs=P(), check_vma=False))

    planned, full = reduce_with(rplan), reduce_with(None)
    hlo = planned.lower(grads).compile().as_text()
    coll = analyze_hlo(hlo)["coll_bytes"]
    out_p = jax.device_get(planned(grads))
    out_f = jax.device_get(full(grads))
    ident = all(np.array_equal(a, b) for a, b in
                zip(jax.tree.leaves(out_p), jax.tree.leaves(out_f)))

    err = align_packed_tree(
        jax.tree.map(lambda g: jnp.zeros(g.shape, jnp.float32), grads),
        params, jnp.float32, trainable)
    comp = jax.jit(lambda g, e: compress_with_feedback(g, e, trainable))
    comp_us = timed(comp, grads, err)

    def frozen_count(g, t):
        if isinstance(t, np.ndarray):
            dead = int((~np.asarray(t, bool)).sum())
            return dead * int(np.prod(g.shape[t.ndim:], dtype=np.int64))
        return 0 if t else int(np.prod(g.shape, dtype=np.int64))

    flat_g = jax.tree_util.tree_flatten(grads)[0]
    flat_t = jax.tree_util.tree_flatten(grads)[1].flatten_up_to(trainable)
    frozen_params = sum(frozen_count(g, t)
                        for g, t in zip(flat_g, flat_t))
    total_params = sum(int(np.prod(g.shape, dtype=np.int64))
                       for g in flat_g)
    prefix = ("freeze_aware_reduce" if mode == "tier1_drop"
              else "freeze_aware_reduce_rowsliced")
    for compress in (False, True):
        rows.append({
            "name": "%s/frozen_%s/%s"
                    % (prefix, frac, "int8_ef" if compress else "fp32"),
            "mode": mode,
            "frozen_frac": frac,
            "frozen_param_frac": round(frozen_params / total_params, 4),
            "compress": compress,
            "mesh": [8],
            "measured_reduce_us": 0.0,
            "measured_compress_us": round(comp_us, 1) if compress else 0.0,
            "hlo_collective_bytes": int(coll),
            "wire_bytes_model": int(reduce_plan_bytes(
                grads, rplan, 1 if compress else 4)),
            "bit_identical_to_full_reduce": bool(ident),
        })
    timers.append((planned, [len(rows) - 2, len(rows) - 1]))

# Interleaved timing: round-robin the reps across every sweep point (same
# `raw` input — the reduce program's cost is data-independent) so a
# persistent load epoch on a shared box inflates all points equally instead
# of corrupting whichever point it overlapped; min-per-point then filters it
# out.  Contiguous per-point timing showed spurious tail inversions here.
for fn, _ in timers:
    jax.tree.leaves(fn(raw))[0].block_until_ready()  # warm
best = [float("inf")] * len(timers)
for _ in range(10):
    for i, (fn, _) in enumerate(timers):
        t0 = time.perf_counter()
        jax.tree.leaves(fn(raw))[0].block_until_ready()
        best[i] = min(best[i], time.perf_counter() - t0)
for i, (_, idxs) in enumerate(timers):
    for j in idxs:
        rows[j]["measured_reduce_us"] = round(best[i] * 1e6, 1)
print("JSON_ROWS " + json.dumps(rows))
"""


def _reduce_rows():
    """Freeze-aware explicit-reduce sweep on 8 host CPU devices, run in a
    subprocess (pinned to the CPU, never on the chip) so this process keeps
    its single-device view.  Measured HLO
    collective bytes and reduce wall time must strictly decrease with the
    frozen fraction; every swept fraction must be bit-identical to the
    full-tree reduce (frozen grads are exactly zero)."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=src)
    try:
        out = subprocess.run([sys.executable, "-c", _REDUCE_BENCH],
                             capture_output=True, text=True, timeout=1800,
                             env=env)
        if out.returncode != 0:
            raise RuntimeError(out.stderr[-500:])
        return json.loads(out.stdout.split("JSON_ROWS", 1)[1])
    except Exception as e:  # keep the rest of the bench usable anywhere
        return [{"name": "freeze_aware_reduce/unavailable",
                 "note": str(e)[:500]}]


def run():
    rows = []
    L, M, N = 4, 256, 1024
    g = jax.random.normal(jax.random.PRNGKey(0), (L, M, N), jnp.float32)
    prev = jnp.zeros_like(g)

    jnp_version = jax.jit(lambda g, p: (
        jnp.sum(jnp.abs(g - p), axis=(1, 2)), g))
    rows.append({
        "name": "grades_norm/pallas-interpret",
        "us_per_call": round(_time(ops.grades_norm, g, prev), 1),
        "derived": "3 HBM passes (2R+1W)"})
    rows.append({
        "name": "grades_norm/jnp",
        "us_per_call": round(_time(jnp_version, g, prev), 1),
        "derived": "~5 HBM passes (sub, abs, reduce, copy)"})

    p = jax.random.normal(jax.random.PRNGKey(1), (L, M, N))
    m = jnp.zeros_like(p)
    v = jnp.zeros_like(p)
    frozen = jnp.array([False, True, False, True])
    kw = dict(weight_decay=0.01)
    rows.append({
        "name": "masked_adamw/pallas-interpret",
        "us_per_call": round(_time(
            lambda *a: ops.masked_adamw(*a, 1e-3, 1, **kw), p, g, m, v,
            frozen), 1),
        "derived": "frozen layers: flag load only; lr/count dynamic"})
    ref_fn = jax.jit(lambda *a: ref.masked_adamw_ref(
        *a, lr=1e-3, b1=0.9, b2=0.95, eps=1e-8, count=1, **kw))
    rows.append({
        "name": "masked_adamw/jnp",
        "us_per_call": round(_time(ref_fn, p, g, m, v, frozen), 1),
        "derived": "frozen layers: full RMW streamed"})

    from repro.kernels.flash_attention import flash_attention
    B, S, KV, G, hd = 2, 256, 2, 1, 64
    q = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, G, hd))
    k = jax.random.normal(jax.random.PRNGKey(3), (B, S, KV, hd))
    vv = jax.random.normal(jax.random.PRNGKey(4), (B, S, KV, hd))
    rows.append({
        "name": "flash_attention/pallas-interpret",
        "us_per_call": round(_time(
            lambda *a: (flash_attention(*a, block_q=128, block_k=128),), q, k, vv), 1),
        "derived": "O(bq*bk) score memory"})
    ref_attn = jax.jit(lambda q, k, v: (ref.flash_attention_ref(q, k, v),))
    rows.append({
        "name": "flash_attention/jnp",
        "us_per_call": round(_time(ref_attn, q, k, vv), 1),
        "derived": "O(S^2) score memory"})

    step_rows = _fused_step_rows()
    rows.extend(step_rows)
    attn_rows = _attention_rows()
    rows.extend(attn_rows)
    sharded_rows = _sharded_step_rows()
    rows.extend(sharded_rows)
    reduce_rows = _reduce_rows()
    rows.extend(reduce_rows)
    segment_rows = _segment_rows()
    rows.extend(segment_rows)
    loop_rows = _loop_overhead_rows()
    rows.extend(loop_rows)
    guard_rows = _guard_overhead_rows()
    rows.extend(guard_rows)

    with open(out_path("kernels.json"), "w") as f:
        json.dump(rows, f, indent=1)
    with open(REPO_BENCH, "w") as f:
        json.dump({
            "bench": "fused GradES step (monitor + masked update) vs jnp",
            "backend": jax.default_backend(),
            "note": ("off-TPU the us/speedup columns are the HBM-roofline "
                     "model (measured_* are interpret-mode emulation, not "
                     "TPU time); on TPU they are measured"),
            "rows": step_rows,
            "attention_note": ("fwd+bwd attention sweep, flash kernels vs "
                               "blockwise-jnp: hbm_bytes_* are the §8 "
                               "roofline traffic model (flash keeps score "
                               "tiles in VMEM; jnp round-trips the touched "
                               "(S×T) area), modeled_* divide by HBM_BW; "
                               "off-TPU only the small anchor row is "
                               "measured (interpret emulation)"),
            "attention_rows": attn_rows,
            "sharded_note": ("shard-mapped fused step on a host (2 data, "
                             "4 model) mesh of 8 placeholder CPU devices; "
                             "modeled columns are the per-device HBM "
                             "roofline, measured are emulation"),
            "sharded_rows": sharded_rows,
            "reduce_note": ("freeze-aware explicit DP reduce (DESIGN.md §3) "
                            "on an 8-device host ('data',) mesh: measured "
                            "HLO collective bytes and reduce wall time under "
                            "the boundary ReducePlan vs frozen fraction, "
                            "bit-identity vs the full-tree reduce at every "
                            "fraction, and wire_bytes_model = live elements "
                            "x 1B (int8-EF) vs 4B (fp32) for the cross-pod "
                            "leg.  tier1_drop rows freeze whole types "
                            "(leaves drop outright -> bytes AND time "
                            "strictly decrease); rowsliced rows freeze "
                            "per-layer (live ranges pmean'd into a zeros "
                            "buffer -> bytes strictly decrease, time pays a "
                            "stitch overhead visible at low fractions on "
                            "the CPU emulation)"),
            "reduce_rows": reduce_rows,
            "segment_note": ("Tier-1.5 segmented layer scan (DESIGN.md §2): "
                             "full train step at per-layer frozen fractions "
                             "× segment_max; segment_max=1 is the monolithic "
                             "baseline; modeled_dw_flops is the §8 roofline "
                             "dW term and measured_step_us is real XLA "
                             "compute (dW einsums dropped at trace time on "
                             "any backend)"),
            "segment_rows": segment_rows,
            "loop_note": ("sync-boundary trainer sweep (DESIGN.md §4): "
                          "steady-state per-step time (watchdog p50 of block "
                          "completion deltas, compile excluded) for "
                          "sync_interval 1/8/32 × prefetch on/off on a tiny "
                          "model; host_overhead_us_per_step subtracts the "
                          "compiled-block device floor and shrinks as the "
                          "host wakes once per K steps"),
            "loop_rows": loop_rows,
            "guard_note": ("numerics guard on/off (DESIGN.md §4): the "
                           "all-finite sentinel rides the existing per-block "
                           "metrics (two isfinite ops on already-computed "
                           "scalars + one (K,) float in the bulk transfer); "
                           "modeled_flops_overhead_pct is the compiled-"
                           "program FLOP delta (deterministic) and the "
                           "paired-block wall-clock delta must stay within "
                           "max(1%, noise floor)"),
            "guard_rows": guard_rows,
        }, f, indent=1)
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
