"""Compile every Pallas kernel of the main path for a described TPU v5e chip,
at the published widths of qwen3-0.6b (d 1024, 16/8 heads, hd 128, ff 3072,
28 layers), and one whole GradES train block of that model, which must also
fit the chip's 16 GiB by ``memory_analysis()``.

Nothing runs: the TPU compiler that ships with JAX lowers each kernel for a
chip that is described, not attached, and refuses what the chip would refuse
(block shapes off the 8x128 tiling, loads from memory a kernel may not read,
matmul accumulators the MXU does not take).  Interpret-mode tests cannot see
any of that.

Keep these tests in this one file.  The topology is described inside a
fixture, never at import: only one process may load the TPU library at a
time, so the pytest-xdist worker that is given this file loads it, and the
other workers never try.  Compiles run in this process with JAX's persistent
compilation cache off (``conftest.py`` turns it off for every test).
"""
import contextlib
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.config import GradESConfig, TrainConfig
from repro.core.grades import build_monitor_spec
from repro.core.partition import freeze_plan
from repro.distributed import explicit_reduce_axes, make_mesh, use_mesh
from repro.kernels import compiled_kernels, ops
from repro.kernels.decode_attention import paged_decode_attention
from repro.kernels.dispatch import KernelBackend
from repro.kernels.flash_attention import flash_attention
from repro.launch.mesh import rules_for
from repro.optim.optimizer import init_opt_state
from repro.train.state import init_train_state
from repro.train.step import make_multi_step

L, D, QD, FF = 28, 1024, 2048, 3072               # qwen3-0.6b widths
KV, G, HD = 8, 2, 128
HBM_BYTES = 16 * 2**30                            # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compile_kernels(fn, shapes, one_chip):
    """Compile ``fn`` for the described chip; Pallas kernels by name."""
    assert not jax.config.jax_enable_compilation_cache
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return compiled_kernels(jax.jit(fn).lower(*args).compile().as_text())


def test_grades_norm_compiles(one_chip):
    found = compile_kernels(
        lambda g, p, f: ops.grades_norm(g, p, f, interpret=False),
        [((L, D, QD), jnp.float32), ((L, D, QD), jnp.bfloat16),
         ((L,), jnp.bool_)], one_chip)
    assert found == {"grades_norm": 1}


@pytest.mark.parametrize("optimizer", ["adamw", "sgd"])
def test_masked_update_compiles(optimizer, one_chip):
    leaf = ((L, D, FF), jnp.float32)
    if optimizer == "adamw":
        fn = lambda p, g, m, v, f, lr, c: ops.masked_adamw(  # noqa: E731
            p, g, m, v, f, lr, c, interpret=False)
        shapes = [leaf] * 4 + [((L,), jnp.bool_), ((), jnp.float32),
                               ((), jnp.float32)]
    else:
        fn = lambda p, g, m, f, lr: ops.masked_sgd(  # noqa: E731
            p, g, m, f, lr, interpret=False)
        shapes = [leaf] * 3 + [((L,), jnp.bool_), ((), jnp.float32)]
    assert compile_kernels(fn, shapes, one_chip) == {f"masked_{optimizer}": 1}


@pytest.mark.parametrize("batch,masked", [(1, False), (2, True)])
def test_flash_fwd_bwd_compiles(batch, masked, one_chip):
    S = 512
    shapes = [((batch, S, KV, G, HD), jnp.bfloat16),
              ((batch, S, KV, HD), jnp.bfloat16),
              ((batch, S, KV, HD), jnp.bfloat16),
              ((batch, S), jnp.bool_)]

    def loss(q, k, v, valid):
        o = flash_attention(q, k, v, causal=True,
                            kv_valid=valid if masked else None,
                            interpret=False)
        return o.astype(jnp.float32).sum()

    fwd = compile_kernels(lambda *a: flash_attention(
        *a[:3], causal=True, kv_valid=a[3] if masked else None,
        interpret=False), shapes, one_chip)
    assert fwd == {"flash_fwd": 1}
    bwd = compile_kernels(jax.grad(loss, argnums=(0, 1, 2)), shapes, one_chip)
    assert bwd == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}


def test_latent_attention_and_expert_matmuls_compile(one_chip):
    """Moonlight-16B-A3B's widths: flash with q·k over 192 and P·V over 128
    (16 heads, G = 1), and the held experts' grouped matmuls, fwd + bwd."""
    from repro.config import MoEConfig
    from repro.models import moe
    S, H = 512, 16
    shapes = [((1, S, H, 1, 192), jnp.bfloat16), ((1, S, H, 192), jnp.bfloat16),
              ((1, S, H, 128), jnp.bfloat16)]

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, interpret=False)
        return o.astype(jnp.float32).sum()

    found = compile_kernels(jax.grad(loss, argnums=(0, 1, 2)), shapes,
                            one_chip)
    assert found == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}
    cfg = MoEConfig(n_experts=64, top_k=6, d_ff=1408, scoring="sigmoid",
                    n_held=8)

    def expert_loss(x, router, bias, wg, wu, wd):
        p = dict(router=router, router_bias=bias, w_gate=wg, w_up=wu,
                 w_down=wd)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(moe, "resolve_interpret", lambda _: False)
            out, _ = moe.held_expert_block(x, p, cfg)
        return out.astype(jnp.float32).sum()

    found = compile_kernels(jax.grad(expert_loss, argnums=(0, 3, 4, 5)), [
        ((1, S, D * 2), jnp.bfloat16), ((D * 2, 64), jnp.float32),
        ((64,), jnp.float32), ((8, D * 2, 1408), jnp.bfloat16),
        ((8, D * 2, 1408), jnp.bfloat16), ((8, 1408, D * 2), jnp.bfloat16)],
        one_chip)
    assert found["gmm"] == 6 and found["tgmm"] == 3, found


@pytest.mark.parametrize("kv,g,hd,page_size,pages_per_split,budget", [
    (KV, G, HD, 8, 0, 160),     # qwen3-0.6b, 160-token budget per slot
    (KV, G, HD, 16, 4, 160),    # qwen3-0.6b, several splits per sequence
    (5, 5, 64, 16, 0, 1024),    # hymba-1.5b: the 1024-token SWA ring
    (20, 1, 64, 16, 0, 448),    # whisper-large-v3 decoder
    (8, 8, 112, 16, 0, 256),    # kimi-k2
], ids=["qwen3-ps8", "qwen3-ps16-split4", "hymba-swa", "whisper", "kimi"])
def test_paged_decode_compiles(kv, g, hd, page_size, pages_per_split, budget,
                               one_chip):
    """Any head layout lowers: the kernel fetches whole pages, whose last
    two dims (KV, hd) are the pool's own."""
    B, P = 8, budget // page_size         # 8 slots
    N = 1 + B * P                         # trash page + every slot full
    shapes = [((B, 1, kv, g, hd), jnp.bfloat16),
              ((N, page_size, kv, hd), jnp.bfloat16),
              ((N, page_size, kv, hd), jnp.bfloat16),
              ((B, P), jnp.int32), ((B,), jnp.int32)]
    found = compile_kernels(lambda *a: paged_decode_attention(
        *a, pages_per_split=pages_per_split, interpret=False), shapes,
        one_chip)
    assert found == {"paged_decode": 1}


@pytest.mark.parametrize("chips", [1, 4])
def test_train_block_compiles_and_fits(chips, topo, one_chip):
    """The trainer's sync block (2 steps of 4 x 128 tokens, full remat, GradES
    on) at full width: fp32 master weights, AdamW moments, the bf16 GradES
    ``prev`` buffers, gradients and activations on each chip.  On four chips
    it is the pure data-parallel step with the explicit reduce: replicated
    state, batch split over ``data``, the kernels shard_mapped by hand (the
    TPU lowering cannot partition a Mosaic kernel by itself)."""
    cfg = configs.get("qwen3-0.6b")
    tcfg = TrainConfig(seq_len=128, global_batch=4, sync_interval=2,
                       remat="full", grades=GradESConfig(enabled=True))
    mesh = (make_mesh((chips,), ("data",), devices=topo.devices[:chips])
            if chips > 1 else None)
    state_sh = NamedSharding(mesh, P()) if mesh else one_chip
    batch_sh = NamedSharding(mesh, P(None, "data")) if mesh else one_chip
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=state_sh),
        jax.eval_shape(lambda k: init_train_state(k, cfg, tcfg),
                       jax.random.PRNGKey(0)))
    block = {k: jax.ShapeDtypeStruct((2, 4, 128), jnp.int32,
                                     sharding=batch_sh)
             for k in ("tokens", "labels")}
    spec = build_monitor_spec(state.params)
    with (use_mesh(mesh, rules_for(mesh)) if mesh
          else contextlib.nullcontext()):
        if mesh:
            assert explicit_reduce_axes(mesh, tcfg) == ("data",)
        step = make_multi_step(cfg, tcfg, spec, backend=KernelBackend(
            "pallas", interpret=False, mesh=mesh))
        compiled = jax.jit(step, donate_argnums=0).lower(state,
                                                         block).compile()
    mem = compiled.memory_analysis()    # bytes per device
    peak = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert peak < HBM_BYTES, peak
    n_leaves = sum(len(paths) for paths, _ in spec.groups.values())
    found = compiled_kernels(compiled.as_text())
    assert found["grades_norm"] == found["masked_adamw"] == n_leaves, found
    assert found["flash_dq"] == found["flash_dkv"] == 1, found
    assert found["flash_fwd"] >= 1, found


def test_packed_rows_are_written_back_in_place(one_chip):
    """Tier 1.5 at qwen3-0.6b's widths, in the trainer's sync block: the MLP
    of the lowest 16 layers frozen (one run of live rows in each leaf) and
    wq frozen in layers 8-15 (two runs), moments packed to the live rows.
    The update writes each run back into the donated stack in place, so no
    computation of the compiled block's steps copies a whole packed leaf.  (A
    scatter there lowers to a loop over the live rows that copies the stack
    in and out of its carry on every trip.)  The block's entry may still
    change a leaf's layout, once per block: a packed ``wq`` goes through the
    scan in the layout its matmuls read."""
    cfg = configs.get("qwen3-0.6b")
    tcfg = TrainConfig(seq_len=128, global_batch=4, sync_interval=2,
                       remat="full", grades=GradESConfig(enabled=True))
    params = jax.eval_shape(lambda k: init_train_state(k, cfg, tcfg).params,
                            jax.random.PRNGKey(0))
    spec = build_monitor_spec(params)
    frozen = {n: np.zeros(spec.mask_shape(params, n), bool)
              for n in spec.groups}
    for name in ("layers/w_gate", "layers/w_up", "layers/w_down"):
        frozen[name][:16] = True
    frozen["layers/wq"][8:16] = True
    freeze = freeze_plan(frozen, params, spec, cfg.n_layers, tcfg.segment_max)
    trainable = freeze.trainable

    def init(key):
        st = init_train_state(key, cfg, tcfg)
        return dataclasses.replace(
            st, opt=init_opt_state(st.params, tcfg, trainable))

    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(init, jax.random.PRNGKey(0)))
    block = {k: jax.ShapeDtypeStruct((2, 4, 128), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    step = make_multi_step(cfg, tcfg, spec, freeze, backend=KernelBackend(
        "pallas", interpret=False))
    marks = []

    def listen(event, _t0, _t1, **attrs):
        if event == "/repro/train/packed_rows":
            marks.append(attrs)

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        text = jax.jit(step, donate_argnums=0).lower(state,
                                                     block).compile().as_text()
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    assert [(m["leaves"], m["rows"], m["runs"]) for m in marks] == [
        (4, 56, 5)], marks
    stacked = {"f32[{}]".format(",".join(map(str, p.shape)))
               for p, t in zip(jax.tree.leaves(params),
                               jax.tree.leaves(trainable))
               if isinstance(t, np.ndarray)}
    per_step = set()       # copies outside the block's entry computation
    for comp in re.split(r"\n(?=\S)", text):
        if not comp.startswith("ENTRY"):
            per_step |= set(re.findall(r"(f32\[[\d,]+\])\{[^}]*\} copy\(",
                                       comp))
    assert len(stacked) == 3 and not stacked & per_step, stacked & per_step
