"""Plain reference of a GradES fine-tune of a dense decoder (Llama / Qwen3
block without q/k norms), in float32 at ``Precision.HIGHEST``.

It follows the published description and takes nothing from the program
under test.  Per step:

* loss: the mean next-token cross-entropy over every token of the batch.
  Each layer is ``x + attn(rms(x))`` then ``x + mlp(rms(x))``; RMSNorm gains
  are offsets from 1; rotary embedding of the two halves of each head
  (``rope_theta``); grouped-query causal softmax attention scaled by
  ``head_dim ** -0.5``; SwiGLU MLP; a final RMSNorm and the head (the
  embedding's transpose where tied).
* frozen matrices and layer rows are constants: they get no gradient, no
  update and no monitor value, while the gradient flows through them.
* the gradient of every other parameter is clipped to a global norm of
  ``grad_clip`` and fed to AdamW (bias-corrected moments, decoupled weight
  decay on every trained parameter), with ``lr`` warmed up linearly over
  ``warmup_frac * steps`` steps (at least one) and cosine-decayed to 0 at
  ``steps``.
* the GradES monitor of each live layer row of a monitored matrix is Eq. 1
  of the paper, normalised: the mean absolute change of that row's gradient
  (unclipped) from the step before.

Memory: the gradient of a batch is summed row by row, each row a scan of
rematerialised layers whose head runs in blocks of tokens; so the peak is
the parameters, AdamW's two moments (none for a wholly frozen matrix), one
gradient and one row's, the live rows of the previous step's monitored
gradient, and the activations of one row.

``precision="fp8"`` is the control: every matmul takes its operands rounded
to float8 (e4m3 forward, e5m2 for the backward's cotangents) with a scale
per tensor, and accumulates in float32, as an fp8 training recipe would.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
MONITORED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
HEAD_BLOCK = 512


def _einsum(sub, a, b):
    return jnp.einsum(sub, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _round(x, dtype):
    """Round to a float8 type with a scale per tensor (amax to its max)."""
    top = float(jnp.finfo(dtype).max)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = jax.lax.stop_gradient(top / amax)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def _fp8_einsum(sub):
    @jax.custom_vjp
    def f(a, b):
        return _einsum(sub, _round(a, jnp.float8_e4m3fn),
                       _round(b, jnp.float8_e4m3fn))

    def fwd(a, b):
        qa, qb = _round(a, jnp.float8_e4m3fn), _round(b, jnp.float8_e4m3fn)
        return _einsum(sub, qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda x, y: _einsum(sub, x, y), *res)
        return vjp(_round(g, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f


def matmuls(precision: str) -> Callable:
    if precision == "float32":
        return _einsum
    if precision == "fp8":
        cache: Dict[str, Callable] = {}
        return lambda sub, a, b: cache.setdefault(sub, _fp8_einsum(sub))(a, b)
    raise ValueError(precision)


def _rms(x, gain, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain)


def _rope(x, theta):
    """x: (S, heads, hd); rotates the two halves of each head."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Reference:
    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 frozen_rows: Dict[str, List[bool]],
                 precision: str = "float32"):
        g = traffic["grades"]
        if g["monitor"] != "delta" or not g["normalize"]:
            raise ValueError("the reference computes the normalised Eq.-1 "
                             "monitor only")
        if traffic["optimizer"]["schedule"] not in ("cosine", "constant"):
            raise ValueError(traffic["optimizer"]["schedule"])
        self.c, self.t = config, traffic
        self.L = int(config["num_hidden_layers"])
        self.frozen = {m: np.asarray(frozen_rows.get(m, [False] * self.L),
                                     bool) for m in MONITORED}
        self.live_rows = {m: np.nonzero(~f)[0] for m, f in self.frozen.items()}
        self._jitted: Dict[str, Callable] = {}
        self.mm = matmuls(precision)

    # ---------------------------------------------------------------- loss
    def _layer(self, x, lp):
        c, mm = self.c, self.mm
        H, KV = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
        hd, eps = int(c["head_dim"]), float(c["rms_norm_eps"])
        S = x.shape[0]
        h = _rms(x, lp["attn_norm"], eps)
        q = _rope(mm("sd,de->se", h, lp["wq"]).reshape(S, H, hd),
                  float(c["rope_theta"]))
        k = _rope(mm("sd,de->se", h, lp["wk"]).reshape(S, KV, hd),
                  float(c["rope_theta"]))
        v = mm("sd,de->se", h, lp["wv"]).reshape(S, KV, hd)
        q = q.reshape(S, KV, H // KV, hd)
        s = mm("skgh,tkh->kgst", q, k) * hd ** -0.5
        causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("kgst,tkh->skgh", p, v).reshape(S, H * hd)
        x = x + mm("se,ed->sd", o, lp["wo"])
        h = _rms(x, lp["mlp_norm"], eps)
        a = jax.nn.silu(mm("sd,df->sf", h, lp["w_gate"])) * mm(
            "sd,df->sf", h, lp["w_up"])
        return x + mm("sf,fd->sd", a, lp["w_down"])

    def _row_loss(self, params, tokens, labels):
        """Summed cross-entropy of one row."""
        c = self.c
        x = params["embed"][tokens]
        frozen = {m: jnp.asarray(f) for m, f in self.frozen.items()}

        def body(x, xs):
            lp, fz = xs
            lp = {k: (jnp.where(fz[k], jax.lax.stop_gradient(w), w)
                      if k in fz else w) for k, w in lp.items()}
            return self._layer(x, lp), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x,
                            (params["layers"], frozen))
        x = _rms(x, params["final_norm"], float(c["rms_norm_eps"]))
        head = (params["embed"].T if c["tie_word_embeddings"]
                else params["lm_head"])
        S = x.shape[0]
        blk = math.gcd(S, HEAD_BLOCK)

        @jax.checkpoint
        def ce(carry, xs):
            xb, lb = xs
            logits = self.mm("sd,dv->sv", xb, head)
            gold = jnp.take_along_axis(logits, lb[:, None], axis=-1)[:, 0]
            return carry + jnp.sum(jax.nn.logsumexp(logits, -1) - gold), None

        total, _ = jax.lax.scan(ce, jnp.float32(0),
                                (x.reshape(S // blk, blk, -1),
                                 labels.reshape(S // blk, blk)))
        return total

    def _grad(self, params, batch):
        """(loss, gradient) of a batch, one row at a time: a scan over the
        rows would hold a second gradient while it accumulates."""
        row_grad = self._jit("row_grad", jax.value_and_grad(self._row_loss))
        add = self._jit("add", lambda a, b: jax.tree.map(jnp.add, a, b),
                        donate_argnums=0)
        scale = self._jit("scale", lambda a, s: jax.tree.map(
            lambda x: x * s, a), donate_argnums=0)
        total, acc = 0.0, None
        for t, l in zip(batch["tokens"], batch["labels"]):
            loss, g = row_grad(params, jnp.asarray(t), jnp.asarray(l))
            total += float(loss)
            acc = g if acc is None else add(acc, g)
            del g
        n = batch["tokens"].size
        return total / n, scale(acc, 1.0 / n)

    def _jit(self, name, fn, **kw):
        if name not in self._jitted:
            self._jitted[name] = jax.jit(fn, **kw)
        return self._jitted[name]

    # -------------------------------------------------------------- update
    def lr(self, count: int) -> float:
        o, steps = self.t["optimizer"], int(self.t["trainer"]["steps"])
        warm = max(int(float(o["warmup_frac"]) * steps), 1)
        lr = float(o["lr"]) * min(count / warm, 1.0)
        if o["schedule"] == "constant":
            return lr
        prog = min(max((count - warm) / max(steps - warm, 1), 0.0), 1.0)
        return lr * 0.5 * (1 + math.cos(math.pi * prog))

    def _live(self, path, leaf):
        """True where the parameter trains, broadcastable over the leaf;
        None where none of it does."""
        if path[0] == "layers" and path[1] in self.frozen:
            if not self.live_rows[path[1]].size:
                return None
            live = ~self.frozen[path[1]]
            return jnp.asarray(live.reshape((-1,) + (1,) * (leaf.ndim - 1)))
        return jnp.ones((), bool)

    def _moments(self, params):
        """AdamW's zero moments; a scalar for a wholly frozen matrix."""
        return jax.tree_util.tree_map_with_path(
            lambda path, p: jnp.zeros(
                () if self._live(tuple(k.key for k in path), p) is None
                else p.shape, p.dtype), params)

    def _update(self, params, grads, m, v, count, lr):
        o = self.t["optimizer"]
        b1, b2, eps = float(o["b1"]), float(o["b2"]), float(o["eps"])
        wd, clip = float(o["weight_decay"]), float(o["grad_clip"])
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))

        def one(path, p, g, m_, v_):
            live = self._live(tuple(k.key for k in path), p)
            if live is None:
                return p, m_, v_
            g = g * scale
            m2 = jnp.where(live, b1 * m_ + (1 - b1) * g, m_)
            v2 = jnp.where(live, b2 * v_ + (1 - b2) * g * g, v_)
            step = lr * (m2 / (1 - b1 ** count)) / (
                jnp.sqrt(v2 / (1 - b2 ** count)) + eps)
            return jnp.where(live, p - step - lr * wd * p, p), m2, v2

        out = jax.tree_util.tree_map_with_path(one, params, grads, m, v)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    def _keep(self, g):
        """The live rows of each monitored gradient, for the next step."""
        return {m: g["layers"][m][idx] for m, idx in self.live_rows.items()
                if idx.size}

    def _monitor(self, g, g_prev):
        """Eq. 1 per live row: mean |g - g_prev| over the row; 0 if frozen."""
        out = {}
        for m, idx in self.live_rows.items():
            out[m] = jnp.zeros((self.L,), jnp.float32)
            if idx.size:
                d = jnp.abs(g["layers"][m][idx] - g_prev[m])
                out[m] = out[m].at[idx].set(
                    jnp.mean(d.reshape(idx.size, -1), axis=1))
        return out

    # ---------------------------------------------------------------- train
    def train(self, params_fn: Callable[[], Any], batches) -> Dict[str, Any]:
        """Runs ``len(batches)`` steps from ``params_fn()``; returns the
        per-step losses and, after the last step, each leaf's change norm,
        the square root of the sum of AdamW's second moment, and the monitor
        of each monitored layer row."""
        update = jax.jit(self._update, donate_argnums=(0, 2, 3))
        keep = jax.jit(self._keep)
        params = params_fn()
        m, v = self._moments(params), self._moments(params)
        losses, g_prev, monitor = [], None, None
        n = len(batches)
        for i, batch in enumerate(batches, start=1):
            loss, g = self._grad(params, batch)
            losses.append(loss)
            if i == n and g_prev is not None:
                monitor = jax.device_get(jax.jit(self._monitor)(g, g_prev))
            g_prev = keep(g) if i == n - 1 else None
            params, m, v = update(params, g, m, v, i, self.lr(i))
            del g
        del m, g_prev
        vnorm = jax.device_get(jax.jit(lambda v: jax.tree.map(
            lambda x: jnp.sqrt(jnp.sum(x)), v))(v))
        del v
        change = jax.device_get(jax.jit(lambda p, p0: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0))(
                params, params_fn()))
        del params
        flat = lambda t: {jax.tree_util.keystr(k, simple=True, separator="/"):
                          float(x) for k, x in
                          jax.tree_util.tree_leaves_with_path(t)}
        return {"losses": losses, "change": flat(change), "grad": flat(vnorm),
                "monitor": {f"layers/{k}": np.asarray(x, np.float64).tolist()
                            for k, x in (monitor or {}).items()}}
