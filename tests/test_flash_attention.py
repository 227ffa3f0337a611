"""Flash-attention production path (DESIGN.md §3b): fwd AND grad parity vs the
``full_attention`` oracle across causal × window × GQA × kv_valid ×
non-block-multiple shapes (interpret mode on CPU — same kernel bodies as TPU),
plus backend routing: per-call jnp fallback for unsupported shapes without
recompiling the step, forced-pallas warnings, and the shard_map wrapper."""
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.masking import NEG_INF
from repro.models.attention import (attention, blockwise_attention,
                                    full_attention)

BQ = BK = 32

#           S    T   KV  G  hd  causal window kv_valid
CASES = [
    ( 64,  64, 2, 1, 32, True,  0,  False),   # plain causal MHA-per-kv
    ( 64,  64, 2, 2, 32, True,  0,  False),   # GQA
    ( 64,  64, 1, 4, 16, False, 0,  False),   # bidirectional GQA
    ( 96,  96, 2, 2, 16, True,  37, False),   # sliding window
    ( 45,  61, 1, 3, 24, True,  0,  True),    # ragged S/T + kv_valid padding
    ( 33,  70, 2, 2, 16, False, 0,  True),    # ragged bidirectional + kv_valid
    ( 96,  96, 1, 4, 64, True,  50, True),    # window × GQA × kv_valid
]


def _inputs(S, T, KV, G, hd, kv_valid, dtype=jnp.float32, B=1):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, KV, hd)).astype(dtype)
    valid = None
    if kv_valid:
        # random masking WITHOUT a keep-first-column guard: rows whose whole
        # causal/window band is masked out are a defined case (exactly zero
        # output/grads on every path — masking.rows_alive).
        valid = jax.random.bernoulli(ks[3], 0.8, (B, T))
    return q, k, v, valid


@pytest.mark.parametrize("S,T,KV,G,hd,causal,window,kv_valid", CASES)
def test_flash_fwd_and_grads_match_oracle(S, T, KV, G, hd, causal, window,
                                          kv_valid):
    q, k, v, valid = _inputs(S, T, KV, G, hd, kv_valid)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)  # fixed cotangent

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) * w)

    flash = functools.partial(flash_attention, causal=causal, window=window,
                              kv_valid=valid, block_q=BQ, block_k=BK)
    oracle = functools.partial(full_attention, causal=causal, window=window,
                               kv_valid=valid)
    lf, gf = jax.value_and_grad(functools.partial(loss, flash),
                                (0, 1, 2))(q, k, v)
    lo, go = jax.value_and_grad(functools.partial(loss, oracle),
                                (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lo), rtol=2e-5,
                               atol=2e-4)
    for a, b, name in zip(gf, go, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


#           S   T  KV  hd   hdv  causal kv_valid
MLA_CASES = [
    (64, 64, 2, 192, 128, True,  False),   # latent attention: q·k 192, P·V 128
    (45, 61, 1, 192, 128, True,  True),    # ragged S/T + kv_valid padding
    (64, 64, 2, 192, 128, False, True),    # bidirectional + kv_valid
]


@pytest.mark.parametrize("S,T,KV,hd,hdv,causal,kv_valid", MLA_CASES)
def test_flash_two_head_dims_match_oracle(S, T, KV, hd, hdv, causal,
                                          kv_valid):
    """q·k over ``hd`` and P·V over ``hdv``: output and all three gradients
    equal ``full_attention``'s, scaled by ``hd ** -0.5``."""
    q, k, _, valid = _inputs(S, T, KV, 1, hd, kv_valid)
    v = jax.random.normal(jax.random.PRNGKey(7), (1, T, KV, hdv))
    w = jax.random.normal(jax.random.PRNGKey(9), (1, S, KV, 1, hdv))

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) * w)

    flash = functools.partial(flash_attention, causal=causal, kv_valid=valid,
                              block_q=BQ, block_k=BK)
    oracle = functools.partial(full_attention, causal=causal, kv_valid=valid)
    lf, gf = jax.value_and_grad(functools.partial(loss, flash),
                                (0, 1, 2))(q, k, v)
    lo, go = jax.value_and_grad(functools.partial(loss, oracle),
                                (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lo), rtol=2e-5,
                               atol=2e-4)
    for a, b, name in zip(gf, go, ("dq", "dk", "dv")):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_flash_bf16_forward():
    q, k, v, _ = _inputs(64, 64, 2, 2, 32, False, dtype=jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=BQ, block_k=BK)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=3e-2,
                               atol=3e-2)


def test_fully_masked_rows_zero_on_all_paths():
    """A fully padded batch entry (all-False kv_valid — the case kv_valid
    exists for) produces exactly zero output AND zero gradients on flash,
    full, and blockwise alike: no backend-dependent garbage."""
    q, k, v, _ = _inputs(32, 32, 2, 2, 16, False, B=2)
    valid = jnp.ones((2, 32), bool).at[1].set(False)
    w = jax.random.normal(jax.random.PRNGKey(4), q.shape)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) * w)

    paths = {
        "flash": functools.partial(flash_attention, causal=True,
                                   kv_valid=valid, block_q=BQ, block_k=BK),
        "full": functools.partial(full_attention, causal=True, kv_valid=valid),
        "blockwise": functools.partial(blockwise_attention, causal=True,
                                       kv_valid=valid, q_chunk=16, kv_chunk=16),
    }
    outs, grads = {}, {}
    for name, fn in paths.items():
        outs[name] = fn(q, k, v)
        grads[name] = jax.grad(functools.partial(loss, fn), (0, 1, 2))(q, k, v)
        assert not np.asarray(outs[name])[1].any(), name     # dead row: zeros
        for g in grads[name]:
            assert not np.asarray(g)[1].any(), name          # and zero grads
    for name in ("full", "blockwise"):
        np.testing.assert_allclose(np.asarray(outs["flash"]),
                                   np.asarray(outs[name]), rtol=2e-5,
                                   atol=2e-5, err_msg=name)
        for a, b in zip(grads["flash"], grads[name]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)


def test_masking_constant_unified():
    """One NEG_INF everywhere — fused and reference paths share masking."""
    import repro.models.attention as attn_mod
    from repro.kernels import flash_attention as fa_mod
    assert attn_mod.NEG_INF is NEG_INF
    assert fa_mod.NEG_INF is NEG_INF


# ---------------------------------------------------------------------------
# Backend routing (models.attention.attention -> kernels.dispatch)
# ---------------------------------------------------------------------------

def test_flash_restriction_reasons():
    ok = ((2, 64, 2, 2, 32), (2, 64, 2, 32))
    assert dispatch.flash_attention_restriction(*ok, jnp.float32) is None
    assert "decode-shaped" in dispatch.flash_attention_restriction(
        (2, 1, 2, 2, 32), (2, 64, 2, 32), jnp.float32)
    assert "sublane" in dispatch.flash_attention_restriction(
        (2, 64, 2, 2, 20), (2, 64, 2, 20), jnp.float32)
    assert "VMEM" in dispatch.flash_attention_restriction(
        (2, 64, 2, 2, 1024), (2, 64, 2, 1024), jnp.float32)
    assert "layout" in dispatch.flash_attention_restriction(
        (2, 64, 32), (2, 64, 32), jnp.float32)
    assert "dtype" in dispatch.flash_attention_restriction(
        (2, 64, 2, 2, 32), (2, 64, 2, 32), jnp.int32)


def test_attention_routes_to_flash_on_pallas(monkeypatch):
    q, k, v, _ = _inputs(64, 64, 2, 2, 32, False)
    calls = []
    real = dispatch.fused_flash_attention

    def spy(*a, **kw):
        calls.append(kw.get("backend"))
        return real(*a, **kw)

    monkeypatch.setattr(dispatch, "fused_flash_attention", spy)
    got = attention(q, k, v, causal=True, backend="pallas")
    assert len(calls) == 1 and calls[0].use_pallas
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full_attention(q, k, v, causal=True)),
                               rtol=2e-5, atol=2e-5)
    # jnp backend and CPU-auto never touch the kernel
    attention(q, k, v, causal=True, backend="jnp")
    attention(q, k, v, causal=True, backend=None)
    assert len(calls) == (2 if jax.default_backend() == "tpu" else 1)


def test_attention_grads_through_routing():
    """jax.grad through the routed entry point: pallas == jnp backends."""
    q, k, v, _ = _inputs(48, 48, 2, 2, 16, False)
    w = jax.random.normal(jax.random.PRNGKey(3), q.shape)

    def loss(backend, q, k, v):
        return jnp.sum(attention(q, k, v, causal=True, window=19,
                                 backend=backend) * w)

    gp = jax.grad(functools.partial(loss, "pallas"), (0, 1, 2))(q, k, v)
    gj = jax.grad(functools.partial(loss, "jnp"), (0, 1, 2))(q, k, v)
    for a, b in zip(gp, gj):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4)


def test_unsupported_shape_falls_back_without_recompile():
    """hd % 8 != 0 cannot take the kernel: forced pallas warns once, routes to
    the blockwise path (chunk_threshold exceeded), and repeated calls reuse
    one compilation — the routing is shape-static, not data-dependent."""
    B, S, KV, G, hd = 1, 16, 2, 2, 20
    q, k, v, _ = _inputs(S, S, KV, G, hd, False, B=B)

    @jax.jit
    def step(q, k, v):
        return attention(q, k, v, causal=True, backend="pallas",
                         chunk_threshold=8, q_chunk=8, kv_chunk=8)

    dispatch._warned_fallbacks.clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        out1 = step(q, k, v)
        out2 = step(q * 2, k, v)
    msgs = [str(r.message) for r in rec if "flash kernel" in str(r.message)]
    assert len(msgs) == 1 and "sublane" in msgs[0]
    assert step._cache_size() == 1
    want = blockwise_attention(q, k, v, causal=True, q_chunk=8, kv_chunk=8)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert not np.allclose(np.asarray(out1), np.asarray(out2))
    dispatch._warned_fallbacks.clear()


def test_auto_backend_fallback_is_silent():
    dispatch._warned_fallbacks.clear()
    q, k, v, _ = _inputs(16, 16, 2, 2, 20, False, B=1)
    auto = dispatch.KernelBackend("pallas", True, forced=False)  # auto-on-TPU
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        attention(q, k, v, causal=True, backend=auto)
    assert not [r for r in rec if "flash kernel" in str(r.message)]
    dispatch._warned_fallbacks.clear()


# ---------------------------------------------------------------------------
# shard_map wrapper (1-device mesh drives the plumbing; the 8-device
# equivalence runs in the slow lane, tests/test_distributed.py)
# ---------------------------------------------------------------------------

def _trivial_mesh(axes=("data", "model")):
    dev = np.asarray(jax.devices()[:1]).reshape((1,) * len(axes))
    return jax.sharding.Mesh(dev, axes)


def test_sharded_flash_matches_local_on_trivial_mesh():
    mesh = _trivial_mesh()
    sharded = dispatch.KernelBackend("pallas", True, mesh, forced=True)
    local = dispatch.KernelBackend("pallas", True)
    q, k, v, valid = _inputs(48, 48, 2, 2, 32, True)
    w = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def loss(backend, q, k, v):
        return jnp.sum(dispatch.fused_flash_attention(
            q, k, v, causal=True, kv_valid=valid, backend=backend,
            block_q=BQ, block_k=BK) * w)

    ls, gs = jax.value_and_grad(functools.partial(loss, sharded),
                                (0, 1, 2))(q, k, v)
    ll, gl = jax.value_and_grad(functools.partial(loss, local),
                                (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(ll), rtol=1e-6)
    for a, b in zip(gs, gl):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_train_step_with_pallas_attention_smoke():
    """One reduced train step with kernels='pallas' drives flash fwd+bwd
    inside value_and_grad end to end (finite loss, finite grads)."""
    import repro.configs as configs
    from repro.config import GradESConfig, TrainConfig
    from repro.core.grades import build_monitor_spec
    from repro.data.pipeline import make_batches
    from repro.train.state import init_train_state
    from repro.train.step import make_train_step

    cfg = configs.reduced("qwen3-0.6b")
    assert cfg.attn_chunk_threshold > 0  # knob is threaded from ModelConfig
    tcfg = TrainConfig(seq_len=16, global_batch=2, steps=1, lr=1e-3,
                       kernels="pallas",
                       grades=GradESConfig(enabled=True, alpha=0.5))
    state = init_train_state(jax.random.PRNGKey(0), cfg, tcfg)
    spec = build_monitor_spec(state.params)
    step = jax.jit(make_train_step(cfg, tcfg, spec,
                                   backend=dispatch.resolve_backend("pallas")))
    for batch in make_batches(cfg, tcfg, steps=1):
        state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
