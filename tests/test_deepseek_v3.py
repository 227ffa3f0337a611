"""The DeepSeek-V3 block (latent attention, sigmoid-routed held experts with
no drops, shared experts, a leading dense layer) against a float32 reference
written here in plain ``jnp``, at a tiny size on the CPU: the loss and every
gradient; the expert layer cut into slices of held experts adds up to the
uncut layer; routing selects by the biased score and weights by the unbiased
one; the trainer leaves frozen ``(layer, expert)`` rows, a whole frozen
type and the correction bias bit-unchanged; and only a sigmoid-scored
router stays float32 in the layer body."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import GradESConfig, ModelConfig, MoEConfig, TrainConfig
from repro.models import model
from repro.models import moe as moe_lib

E, HELD, K = 8, 2, 3
CFG = ModelConfig(
    name="tiny-deepseek-v3", family="moe", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=32, vocab=96, rope_theta=10_000.0,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_dense_layers=1, dense_d_ff=96,
    moe=MoEConfig(n_experts=E, top_k=K, d_ff=32, scoring="sigmoid",
                  routed_scale=2.446, shared_d_ff=32, n_held=HELD,
                  held_offset=2),
    dtype="float32")
HIGHEST = jax.lax.Precision.HIGHEST


def _params(cfg=CFG, seed=0):
    return model.init_params(jax.random.PRNGKey(seed), cfg)


def _batch(B=2, S=24, seed=1):
    t = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0, CFG.vocab)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


# --------------------------------------------------------- the reference
def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, g, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + g)


def _rope(x, theta):
    """x: (B, S, heads, dims), the two halves of each head rotated."""
    S, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(S)[:, None] * theta ** (-jnp.arange(0, d, 2) / d)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _swiglu(h, g, u, d):
    return _mm(jax.nn.silu(_mm(h, g)) * _mm(h, u), d)


def _mla(h, lp, c):
    B, S, _ = h.shape
    H, dn, dr, dv = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim, \
        c.v_head_dim
    q = _mm(h, lp["wq"]).reshape(B, S, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], c.rope_theta)], -1)
    a = _mm(h, lp["wkv_a"])
    lat = _rms(a[..., :c.kv_lora_rank], lp["kv_norm"])
    k_pe = _rope(a[..., None, c.kv_lora_rank:], c.rope_theta)
    kv = _mm(lat, lp["wkv_b"]).reshape(B, S, H, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_pe, H, axis=2)], -1)
    s = jnp.einsum("bqhe,bthe->bhqt", q, k, precision=HIGHEST) \
        * (dn + dr) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqt,bthv->bqhv", jax.nn.softmax(s, -1), kv[..., dn:],
                   precision=HIGHEST)
    return _mm(o.reshape(B, S, H * dv), lp["wo"])


def _experts(h, lp, m: MoEConfig, held=None):
    """The uncut layer's routed terms over the held experts, plus the shared
    experts; ``held`` overrides which experts the weights hold."""
    scores = jax.nn.sigmoid(_mm(h, lp["router"]))
    _, pick = jax.lax.top_k(scores + lp["router_bias"], m.top_k)
    w = jnp.take_along_axis(scores, pick, -1)
    w = w / w.sum(-1, keepdims=True) * m.routed_scale
    out = _swiglu(h, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    for j, e in enumerate(held if held is not None
                          else range(m.held_offset, m.held_offset + m.held)):
        we = jnp.sum(jnp.where(pick == e, w, 0.0), -1, keepdims=True)
        out = out + we * _swiglu(h, lp["w_gate"][j], lp["w_up"][j],
                                 lp["w_down"][j])
    return out


def _layer(x, lp, c, dense):
    x = x + _mla(_rms(x, lp["attn_norm"]), lp, c)
    h = _rms(x, lp["mlp_norm"])
    if dense:
        return x + _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x + _experts(h, lp, c.moe)


def ref_loss(p, batch, c=CFG):
    x = p["embed"][batch["tokens"]]
    for stack, dense in (("dense_layers", True), ("layers", False)):
        n = p[stack]["wq"].shape[0]
        for i in range(n):
            x = _layer(x, jax.tree.map(lambda a: a[i], p[stack]), c, dense)
    logits = _mm(_rms(x, p["final_norm"]), p["lm_head"])
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


# ----------------------------------------------------------------- tests
def test_loss_and_gradients_match_the_reference():
    p, batch = _params(), _batch()
    (loss, metrics), grads = jax.value_and_grad(
        lambda q: model.loss_fn(q, batch, CFG), has_aux=True)(p)
    want, want_g = jax.value_and_grad(ref_loss)(p, batch)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    ref = dict(jax.tree_util.tree_leaves_with_path(want_g))
    for path, g in flat:
        scale = float(jnp.max(jnp.abs(ref[path]))) or 1.0
        np.testing.assert_allclose(g, ref[path], rtol=0, atol=1e-4 * scale,
                                   err_msg=jax.tree_util.keystr(path))
    # the correction bias gets no gradient; every layer's picks add up
    assert not np.asarray(grads["layers"]["router_bias"]).any()
    load = np.asarray(metrics["expert_load"])
    assert load.shape == (CFG.n_layers, HELD) and load.sum() > 0


def test_held_slices_add_up_to_the_uncut_layer():
    """Four chips holding two of the eight experts each: their outputs, with
    the shared experts (which every chip computes alike) counted once, add up
    to the layer with all eight held."""
    whole = dataclasses.replace(CFG.moe, n_held=E, held_offset=0)
    p = jax.tree.map(lambda a: a[0], model.init_params(
        jax.random.PRNGKey(3), dataclasses.replace(CFG, moe=whole))["layers"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, CFG.d_model))
    uncut, load = moe_lib.held_expert_block(x, p, whole)
    shared = _swiglu(x, p["shared_gate"], p["shared_up"], p["shared_down"])
    total, loads = -3 * shared, []
    for first in range(0, E, HELD):
        part = dataclasses.replace(whole, n_held=HELD, held_offset=first)
        sl = {**p, **{t: p[t][first:first + HELD]
                      for t in ("w_gate", "w_up", "w_down")}}
        out, got = moe_lib.held_expert_block(x, sl, part)
        total, loads = total + out, loads + [got]
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.concatenate(loads), load)
    assert int(load.sum()) == 2 * 16 * K       # every pick of every token
    np.testing.assert_allclose(uncut, _experts(x, p, whole, range(E)),
                               rtol=1e-5, atol=1e-5)


def test_routing_selects_by_biased_and_weights_by_unbiased_scores():
    """A bias that reorders the scores changes which experts are picked but
    not the weights the picked ones get."""
    m = dataclasses.replace(CFG.moe, n_held=E, held_offset=0)
    x = jnp.eye(4, CFG.d_model)
    router = jnp.zeros((CFG.d_model, E)).at[0].set(
        jnp.array([3., 2., 1., 0., -1., -2., -3., -4.]))
    bias = jnp.array([0., 0., 0., 0., 0., 0., 0., 10.])
    w, picks = moe_lib.sigmoid_route(x[:1], router, bias, m)
    assert sorted(np.asarray(picks[0]).tolist()) == [0, 1, 7]
    s = jax.nn.sigmoid(router[0])
    want = s[picks[0]] / s[picks[0]].sum() * m.routed_scale
    np.testing.assert_allclose(w[0], want, rtol=1e-6)
    wrong = (s + bias)[picks[0]]
    assert not np.allclose(w[0], wrong / wrong.sum() * m.routed_scale,
                           rtol=1e-2)
    # the whole layer, with the seeded bias, against the reference
    p = jax.tree.map(lambda a: a[0], model.init_params(
        jax.random.PRNGKey(5), dataclasses.replace(CFG, moe=m))["layers"])
    assert np.abs(np.asarray(p["router_bias"])).min() > 0
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 16, CFG.d_model))
    got, _ = moe_lib.held_expert_block(h, p, m)
    np.testing.assert_allclose(got, _experts(h, p, m, range(E)), rtol=1e-5,
                               atol=1e-5)


def test_trainer_keeps_frozen_rows_and_the_bias_unchanged():
    """Pallas kernels (interpret): a whole frozen type (Tier 1), a layer row
    with all its experts frozen (Tier 1.5, segment plan) and a partly frozen
    layer row (Tier 0) stay bit-identical through a repartition boundary;
    live rows move; the correction bias never moves; each drain marks the
    block's held-expert load."""
    from repro.core.grades import build_monitor_spec
    from repro.core.partition import segment_plan
    from repro.train.loop import Trainer
    from repro.train.state import init_train_state
    tcfg = TrainConfig(seq_len=16, global_batch=2, steps=8, sync_interval=2,
                       kernels="pallas", remat="full", prefetch_depth=0,
                       grades=GradESConfig(enabled=True, tau=0.0, alpha=0.0))
    state = init_train_state(jax.random.PRNGKey(0), CFG, tcfg)
    p0 = jax.device_get(state.params)
    experts = np.zeros((CFG.n_layers, HELD), bool)
    experts[0] = True                     # layer row: the plan skips it
    experts[1, 0] = True                  # one (layer, expert): Tier 0
    frozen = dict(state.grades.frozen)
    frozen["layers/wq"] = jnp.ones((CFG.n_layers,), bool)
    frozen["dense_layers/wq"] = jnp.ones((1,), bool)
    for t in ("w_gate", "w_up", "w_down"):
        frozen[f"layers/{t}"] = jnp.asarray(experts)
    state = dataclasses.replace(
        state, grades=dataclasses.replace(state.grades, frozen=frozen))
    spec = build_monitor_spec(state.params)
    plan = segment_plan(jax.device_get(frozen), spec, CFG.n_layers, 8)
    assert plan.segments[0][:2] == (0, 1) and "w_up" in plan.segments[0][2]

    seen = []

    def listen(name, start, end, **kw):
        if name == "/repro/train/expert_load":
            seen.append(kw)

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        res = Trainer(CFG, tcfg, repartition_interval=2).train(state=state)
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    p1 = jax.device_get(res.state.params)
    lay0, lay1 = p0["layers"], p1["layers"]
    for t in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(lay1[t][experts], lay0[t][experts])
        assert not np.array_equal(lay1[t][~experts], lay0[t][~experts]), t
    np.testing.assert_array_equal(lay1["wq"], lay0["wq"])
    np.testing.assert_array_equal(p1["dense_layers"]["wq"],
                                  p0["dense_layers"]["wq"])
    np.testing.assert_array_equal(lay1["router_bias"], lay0["router_bias"])
    assert not np.array_equal(lay1["router"], lay0["router"])
    assert len(seen) == 4 and all(s["assigned"] > 0 for s in seen)
    # layer 1 expert 0 is frozen; no live row of layer 0
    assert all(0 < s["assigned_live"] < s["assigned"] for s in seen)
    assert all(0 < s["busiest"] <= s["assigned"] for s in seen)


def test_only_a_sigmoid_router_stays_float32():
    """DeepSeek-V3's router and correction bias keep float32 in the layer
    body; GShard's softmax router takes the compute dtype, as it did."""
    from repro.models import transformer
    lp = {"router": jnp.ones((4, E)), "router_bias": jnp.ones(E),
          "wq": jnp.ones((4, 4))}
    sig = dataclasses.replace(CFG, dtype="bfloat16")
    got = transformer._compute_dtype(lp, sig)
    assert {k: a.dtype for k, a in got.items()} == {
        "router": jnp.float32, "router_bias": jnp.float32,
        "wq": jnp.bfloat16}
    soft = dataclasses.replace(
        sig, moe=dataclasses.replace(sig.moe, scoring="softmax"))
    got = transformer._compute_dtype({"router": lp["router"]}, soft)
    assert got["router"].dtype == jnp.bfloat16
