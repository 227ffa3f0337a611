"""Plain reference of a GradES fine-tune of the DeepSeek-V3 block (Moonlight,
Kimi-K2) on one chip's share of its experts, in float32 at
``Precision.HIGHEST``.

It follows the published description and takes nothing from the program
under test or from its model family.  The optimizer, the schedule, the
gradient of a batch row by row and the float8 control are the dense
reference's (``references/dense.py``); this module adds the block and the
frozen rows of its experts.  Per layer, ``x + attn(rms(x))`` then
``x + mlp(rms(x))``, RMSNorm gains as offsets from 1:

* latent attention (MLA), no q LoRA: ``q = h W_q`` in heads of ``nope +
  rope`` dims; ``[c_kv, k_pe] = h W_kv_a``, ``c_kv`` RMSNorm'd;
  ``[k_nope, v] = c_kv W_kv_b``; ``k_pe`` is one rope head that every head
  shares; rotary embedding of the two halves of each rope head
  (``rope_theta``); causal softmax over ``q . k`` (``nope + rope`` dims,
  scaled by their count ** -0.5) in blocks of queries, so that a row of 8192
  tokens fits; ``P V`` over ``v`` dims; ``W_o``.
* the leading ``first_k_dense_replace`` layers: a SwiGLU MLP.
* the others: the router's ``router_experts`` sigmoid scores in float32;
  each token picks the ``num_experts_per_tok`` best of score plus the
  correction bias (``router_bias``, a constant) and weights them by their
  unbiased scores, normalised over the picks (``norm_topk_prob``) and scaled
  by ``routed_scaling_factor``.  Of the picks, only the experts this chip
  holds (``held_expert_offset`` and the ``n_routed_experts`` after it) add a
  term: each held expert's SwiGLU runs on every token, times the token's
  weight for it, which is 0 unless the token picked it; nothing is dropped.
  The shared experts (one SwiGLU of ``n_shared_experts`` times the expert
  width) are added for every token.
* frozen matrices, layer rows and ``(layer, expert)`` rows are constants:
  no gradient, update or monitor value, while the gradient flows through
  them; the correction bias is a constant too.  The Eq.-1 monitor is taken
  per live row: a layer of a matrix type, or a ``(layer, expert)`` of the
  routed experts.

The rows frozen at set-up: the matrix types of ``frozen_rows`` over all the
model's layers, the dense ones first, and the ``(layer, expert)`` rows under
the traffic's ``frozen`` / ``expert_rows`` (layers over the whole model,
experts over the held ones).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from references import dense
from references.dense import _rms, _rope

ATTENTION = ("wq", "wkv_a", "wkv_b", "wo")
MLP = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
Q_BLOCK = 1024


class Reference(dense.Reference):
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
        self.Ld = int(config["first_k_dense_replace"])
        self.Le = int(config["num_hidden_layers"]) - self.Ld
        self.Eh = int(config["n_routed_experts"])
        self.masks = self._frozen_masks(frozen_rows,
                                        traffic.get("frozen", {}))
        self.live_idx = {k: np.nonzero(~m.reshape(-1))[0]
                         for k, m in self.masks.items()}
        self._jitted: Dict[str, Callable] = {}
        self.mm = dense.matmuls(precision)

    def _frozen_masks(self, frozen_rows, frozen) -> Dict[str, np.ndarray]:
        """Group (``<stack>/<type>``) -> frozen rows, for every monitored
        matrix type: ``(layers,)``, or ``(layers, held experts)``."""
        Ld, Le, Eh = self.Ld, self.Le, self.Eh
        types = {"dense_layers": ATTENTION + MLP,
                 "layers": ATTENTION + ("router",) + SHARED + MLP}
        out = {}
        for stack, names in types.items():
            lo, n = (0, Ld) if stack == "dense_layers" else (Ld, Le)
            for t in names:
                rows = np.asarray(frozen_rows.get(t, [False] * (Ld + Le)),
                                  bool)[lo:lo + n]
                if stack == "layers" and t in MLP:
                    rows = np.repeat(rows[:, None], Eh, axis=1)
                out[f"{stack}/{t}"] = rows.copy()
        for group in frozen.get("expert_rows", []):
            for t in group["types"]:
                for layer in group["layers"]:
                    out[f"layers/{t}"][layer - Ld, group["experts"]] = True
        return out

    # ---------------------------------------------------------------- loss
    def _swiglu(self, h, gate, up, down):
        mm = self.mm
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", h, gate))
                  * mm("sd,df->sf", h, up), down)

    def _attention(self, h, lp):
        c, mm = self.c, self.mm
        H, r = int(c["num_attention_heads"]), int(c["kv_lora_rank"])
        dn, dr = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
        dv, theta = int(c["v_head_dim"]), float(c["rope_theta"])
        S = h.shape[0]
        q = mm("sd,de->se", h, lp["wq"]).reshape(S, H, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
        kv_a = mm("sd,de->se", h, lp["wkv_a"])
        c_kv = _rms(kv_a[:, :r], lp["kv_norm"], float(c["rms_norm_eps"]))
        k_pe = _rope(kv_a[:, None, r:], theta)
        kv = mm("sr,re->se", c_kv, lp["wkv_b"]).reshape(S, H, dn + dv)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_pe, (S, H, dr))], -1)
        v = kv[..., dn:]
        blk = math.gcd(S, Q_BLOCK)

        @jax.checkpoint
        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk)
            s = mm("qhe,the->hqt", qb, k) * (dn + dr) ** -0.5
            rows = i * blk + jnp.arange(blk)
            causal = jnp.arange(S)[None, :] <= rows[:, None]
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
            return mm("hqt,thv->qhv", p, v)

        o = jax.lax.map(block, jnp.arange(S // blk)).reshape(S, H * dv)
        return mm("se,ed->sd", o, lp["wo"])

    def _experts(self, h, lp):
        """The held experts' terms of the routed output, and the shared
        experts'."""
        c, mm = self.c, self.mm
        k = int(c["num_experts_per_tok"])
        scores = jax.nn.sigmoid(mm("sd,de->se", h, lp["router"]))
        _, picked = jax.lax.top_k(scores + lp["router_bias"], k)
        w = jnp.take_along_axis(scores, picked, axis=-1)
        if c["norm_topk_prob"]:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        w = w * float(c["routed_scaling_factor"])
        out = self._swiglu(h, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"])
        first = int(c["held_expert_offset"])
        for j in range(self.Eh):
            weight = jnp.sum(jnp.where(picked == first + j, w, 0.0), axis=-1)
            out = out + weight[:, None] * self._swiglu(
                h, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
        return out

    def _layer(self, x, lp, dense_mlp: bool):
        eps = float(self.c["rms_norm_eps"])
        x = x + self._attention(_rms(x, lp["attn_norm"], eps), lp)
        h = _rms(x, lp["mlp_norm"], eps)
        if dense_mlp:
            return x + self._swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
        return x + self._experts(h, lp)

    def _stack(self, x, layers, stack: str):
        fz = {k[len(stack) + 1:]: jnp.asarray(m)
              for k, m in self.masks.items() if k.startswith(stack + "/")}

        def body(x, xs):
            lp, f = xs
            lp = {k: (jnp.where(f[k].reshape(f[k].shape + (1,) * (
                      w.ndim - f[k].ndim)), jax.lax.stop_gradient(w), w)
                      if k in f else w) for k, w in lp.items()}
            if "router_bias" in lp:
                lp["router_bias"] = jax.lax.stop_gradient(lp["router_bias"])
            return self._layer(x, lp, stack == "dense_layers"), None

        x, _ = jax.lax.scan(jax.checkpoint(body), x, (layers, fz))
        return x

    def _row_loss(self, params, tokens, labels):
        """Summed cross-entropy of one row."""
        c = self.c
        x = params["embed"][tokens]
        x = self._stack(x, params["dense_layers"], "dense_layers")
        x = self._stack(x, params["layers"], "layers")
        x = _rms(x, params["final_norm"], float(c["rms_norm_eps"]))
        head = (params["embed"].T if c["tie_word_embeddings"]
                else params["lm_head"])
        S = x.shape[0]
        blk = math.gcd(S, dense.HEAD_BLOCK)

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

    # -------------------------------------------------------------- update
    def _live(self, path, leaf):
        """True where the parameter trains, broadcastable over the leaf;
        None where none of it does (the correction bias, frozen types)."""
        if path[-1] == "router_bias":
            return None
        mask = self.masks.get("/".join(path))
        if mask is None:
            return jnp.ones((), bool)
        if mask.all():
            return None
        return jnp.asarray(~mask).reshape(mask.shape + (1,) * (
            leaf.ndim - mask.ndim))

    def _rows(self, g, group):
        stack, t = group.split("/")
        leaf = g[stack][t]
        n = self.masks[group].ndim
        return leaf.reshape((-1,) + leaf.shape[n:])[self.live_idx[group]]

    def _keep(self, g):
        """The live rows of each monitored gradient, for the next step."""
        return {k: self._rows(g, k) for k, idx in self.live_idx.items()
                if idx.size}

    def _monitor(self, g, g_prev):
        """Eq. 1 per live row: mean |g - g_prev| over the row; 0 if frozen."""
        out = {}
        for k, idx in self.live_idx.items():
            flat = jnp.zeros((self.masks[k].size,), jnp.float32)
            if idx.size:
                d = jnp.abs(self._rows(g, k) - g_prev[k])
                flat = flat.at[idx].set(jnp.mean(d.reshape(idx.size, -1), 1))
            out[k] = flat.reshape(self.masks[k].shape)
        return out

    # ---------------------------------------------------------------- train
    def train(self, params_fn: Callable[[], Any], batches) -> Dict[str, Any]:
        """As the dense reference's, whose monitor keys are ``layers/`` and a
        type; here ``_monitor`` keys them by the whole group name."""
        out = super().train(params_fn, batches)
        out["monitor"] = {k[len("layers/"):]: v for k, v in
                          out["monitor"].items()}
        return out
