"""The DeepSeek-V3 block (``model_type`` ``deepseek_v3``: Moonlight, Kimi-K2):
latent attention (MLA) in every layer, leading dense SwiGLU layers
(``first_k_dense_replace``), then expert layers whose router scores all
``router_experts`` experts by sigmoid and picks ``num_experts_per_tok`` of
them, of which this chip holds ``n_routed_experts`` (one chip's share under
expert parallelism), beside ``n_shared_experts`` shared experts that every
token goes through.

Weights (``Ld`` leading dense layers and ``L`` expert layers, each stack on
its leading axis; ``d`` the hidden size, ``H`` heads, ``q = H * (nope +
rope)``, ``o = H * v``, ``r`` the latent rank, ``E`` the router's experts,
``Eh`` those held, ``f`` an expert's width, ``fs`` the shared experts'):

    embed (V, d)    final_norm (d,)    lm_head (d, V)
    both stacks:    attn_norm, mlp_norm (., d)  wq (., d, q)
                    wkv_a (., d, r + rope)  kv_norm (., r)
                    wkv_b (., r, H * (nope + v))  wo (., o, d)
    dense_layers:   w_gate, w_up (Ld, d, ff)  w_down (Ld, ff, d)
    layers:         router (L, d, E)  router_bias (L, E)
                    w_gate, w_up (L, Eh, d, f)  w_down (L, Eh, f, d)
                    shared_gate, shared_up (L, d, fs)  shared_down (L, fs, d)

``router_bias`` is the correction bias: it moves which experts are picked,
and nothing trains it.

Work a step requires (``bench/flops.py`` says what "required" counts):

* every matrix but the routed experts', and the head: ``2 * tokens`` FLOPs a
  weight forward, the same for dX, and the same for dW of the live rows and
  the head;
* the routed experts at the expected load under uniform routing: each token
  picks ``k`` of ``E`` experts, so ``k * Eh / E`` picks a token land on the
  held ones (6 * 8 / 64 = 0.75 for Moonlight cut to 8 experts), each pick
  ``2 * 3 * d * f`` FLOPs forward, the same for dX, and ``2 * d * f`` for
  each matrix of a live ``(layer, expert)`` row for dW;
* causal attention: ``Q K^T`` over ``nope + rope`` dims and ``P V`` over
  ``v`` dims for the ``S (S + 1) / 2`` causal pairs of each row, and twice
  that for the backward pass, in every layer.

Monitor groups: each stack's matrix types, ``dense_layers/<type>`` frozen per
dense layer, ``layers/<type>`` per expert layer (``(L,)``), and the routed
experts ``layers/w_gate``, ``w_up``, ``w_down`` per expert layer and held
expert (``(L, Eh)``).  The traffic names matrix types over all the model's
layers (``Cell.frozen_rows``: ``all_layers``, ``lower_layers``), the dense
layers first; per ``(layer, expert)`` rows come under ``frozen``'s
``expert_rows``, read here: ``{"types": [...], "layers": [...], "experts":
[...]}``, layers counted over the whole model, experts over the held ones.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np

ATTENTION = ("wq", "wkv_a", "wkv_b", "wo")
MLP = ("w_gate", "w_up", "w_down")
SHARED = ("shared_gate", "shared_up", "shared_down")
F32, BF16 = 4, 2


def _dims(c: Dict[str, Any]) -> Dict[str, int]:
    H = int(c["num_attention_heads"])
    dn, dr = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    return dict(
        Ld=int(c["first_k_dense_replace"]),
        L=int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"]),
        d=int(c["hidden_size"]), H=H, dqk=dn + dr, dn=dn, dr=dr,
        dv=int(c["v_head_dim"]), r=int(c["kv_lora_rank"]),
        ff=int(c["intermediate_size"]), f=int(c["moe_intermediate_size"]),
        fs=int(c["n_shared_experts"]) * int(c["moe_intermediate_size"]),
        E=int(c["router_experts"]), Eh=int(c["n_routed_experts"]),
        k=int(c["num_experts_per_tok"]), V=int(c["vocab_size"]))


def model_config(cell):
    """The program's ``ModelConfig`` for the configuration file."""
    from repro.config import ModelConfig, MoEConfig
    c, m = cell.config, _dims(cell.config)
    if c["scoring_func"] != "sigmoid" or c["topk_method"] != "noaux_tc" \
            or int(c["n_group"]) != 1 or c["q_lora_rank"] is not None:
        raise ValueError(f"{c['name']}: the program runs sigmoid noaux_tc "
                         f"routing over one group, with no q LoRA")
    return ModelConfig(
        name=c["name"], family="moe", n_layers=m["L"], d_model=m["d"],
        n_heads=m["H"], n_kv_heads=m["H"], d_ff=m["f"], vocab=m["V"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        kv_lora_rank=m["r"], qk_nope_head_dim=m["dn"],
        qk_rope_head_dim=m["dr"], v_head_dim=m["dv"],
        n_dense_layers=m["Ld"], dense_d_ff=m["ff"],
        moe=MoEConfig(n_experts=m["E"], top_k=m["k"], d_ff=m["f"],
                      scoring="sigmoid", norm_topk=bool(c["norm_topk_prob"]),
                      routed_scale=float(c["routed_scaling_factor"]),
                      shared_d_ff=m["fs"], n_held=m["Eh"],
                      held_offset=int(c["held_expert_offset"])),
        dtype=c["compute_dtype"], param_dtype=c["param_dtype"])


def _attention_shapes(n: int, m: Dict[str, int]):
    return {"attn_norm": ((n, m["d"]), None),
            "wq": ((n, m["d"], m["H"] * m["dqk"]), -2),
            "wkv_a": ((n, m["d"], m["r"] + m["dr"]), -2),
            "kv_norm": ((n, m["r"]), None),
            "wkv_b": ((n, m["r"], m["H"] * (m["dn"] + m["dv"])), -2),
            "wo": ((n, m["H"] * m["dv"], m["d"]), -2),
            "mlp_norm": ((n, m["d"]), None)}


def shapes(config: Dict[str, Any]):
    """Leaf shapes with their fan-in axis (None for norm gains); the
    correction bias is drawn like a matrix over its experts."""
    m = _dims(config)
    L, Ld, d = m["L"], m["Ld"], m["d"]
    dense = _attention_shapes(Ld, m)
    dense.update({"w_gate": ((Ld, d, m["ff"]), -2),
                  "w_up": ((Ld, d, m["ff"]), -2),
                  "w_down": ((Ld, m["ff"], d), -2)})
    layers = _attention_shapes(L, m)
    layers.update({
        "router": ((L, d, m["E"]), -2), "router_bias": ((L, m["E"]), -1),
        "w_gate": ((L, m["Eh"], d, m["f"]), -2),
        "w_up": ((L, m["Eh"], d, m["f"]), -2),
        "w_down": ((L, m["Eh"], m["f"], d), -2),
        "shared_gate": ((L, d, m["fs"]), -2),
        "shared_up": ((L, d, m["fs"]), -2),
        "shared_down": ((L, m["fs"], d), -2)})
    out = {"embed": ((m["V"], d), -1), "dense_layers": dense,
           "layers": layers, "final_norm": ((d,), None)}
    if not config["tie_word_embeddings"]:
        out["lm_head"] = ((d, m["V"]), -2)
    return out


def frozen_masks(cell) -> Dict[str, np.ndarray]:
    """Monitor group -> its frozen flags written at set-up: ``(Ld,)`` or
    ``(L,)`` per layer, ``(L, Eh)`` for the routed experts."""
    m = _dims(cell.config)
    Ld, L, Eh = m["Ld"], m["L"], m["Eh"]
    out: Dict[str, np.ndarray] = {}
    for t, rows in cell.frozen_rows().items():
        rows = np.asarray(rows, bool)
        if t in ATTENTION or t in MLP:
            out[f"dense_layers/{t}"] = rows[:Ld].copy()
        if t in MLP:
            out[f"layers/{t}"] = np.repeat(rows[Ld:, None], Eh, axis=1)
        elif t in ATTENTION or t in SHARED or t == "router":
            out[f"layers/{t}"] = rows[Ld:].copy()
    for group in cell.traffic.get("frozen", {}).get("expert_rows", []):
        for t in group["types"]:
            mask = out.setdefault(f"layers/{t}", np.zeros((L, Eh), bool))
            for layer in group["layers"]:
                if not Ld <= layer < Ld + L:
                    raise ValueError(f"expert_rows: layer {layer} is not an "
                                     f"expert layer ({Ld}..{Ld + L - 1})")
                mask[layer - Ld, group["experts"]] = True
    return out


def _matrices(cell) -> List[Tuple[str, int, int]]:
    """(group, weights a row, rows) of every monitored matrix type; a row is
    a layer, or a (layer, held expert) of the routed experts."""
    m = _dims(cell.config)
    d = m["d"]
    attn = {"wq": d * m["H"] * m["dqk"], "wkv_a": d * (m["r"] + m["dr"]),
            "wkv_b": m["r"] * m["H"] * (m["dn"] + m["dv"]),
            "wo": m["H"] * m["dv"] * d}
    out = []
    for stack, n in (("dense_layers", m["Ld"]), ("layers", m["L"])):
        out += [(f"{stack}/{t}", w, n) for t, w in attn.items()]
    out += [(f"dense_layers/{t}", d * m["ff"], m["Ld"]) for t in MLP]
    out += [("layers/router", d * m["E"], m["L"])]
    out += [(f"layers/{t}", d * m["fs"], m["L"]) for t in SHARED]
    out += [(f"layers/{t}", d * m["f"], m["L"] * m["Eh"]) for t in MLP]
    return out


def _live_rows(cell) -> Dict[str, int]:
    masks = frozen_masks(cell)
    return {g: rows - int(np.sum(masks.get(g, False)))
            for g, _, rows in _matrices(cell)}


def _is_routed(group: str) -> bool:
    return group.startswith("layers/") and group[len("layers/"):] in MLP


def attention_flops_per_call(cell) -> int:
    """One layer's ``Q K^T`` (over ``nope + rope`` dims) plus ``P V`` (over
    ``v`` dims) over every row: the forward pass, and equally each of the
    two backward kernels' required pair of matmuls (``dP`` and ``dQ``;
    ``dV`` and ``dK``)."""
    m = _dims(cell.config)
    return 2 * m["H"] * (m["dqk"] + m["dv"]) * cell.causal_pairs


def step_flops(cell) -> int:
    """FLOPs one training step requires (see the module docstring)."""
    m = _dims(cell.config)
    T = cell.tokens_per_step
    head = m["d"] * m["V"]
    live = _live_rows(cell)
    picks = T * m["k"] * m["Eh"] / m["E"]     # expected picks of held experts
    total = 0.0
    for g, w, rows in _matrices(cell):
        per = picks / m["Eh"] if _is_routed(g) else T
        total += 2 * per * w * (2 * rows + live[g])   # forward, dX, live dW
    total += 2 * T * head * 3                          # the head
    layers = m["Ld"] + m["L"]
    return int(round(total)) + 3 * layers * attention_flops_per_call(cell)


def flash_bytes_per_call(cell) -> Dict[str, int]:
    """Least HBM traffic of one call of each flash kernel: every operand
    read once and every result written once (bf16 activations, keys
    materialised per head, f32 log-sum-exp and ``D`` rows)."""
    m = _dims(cell.config)
    T = cell.tokens_per_step
    qk = T * m["H"] * m["dqk"] * BF16          # q, k, dq or dk
    v = T * m["H"] * m["dv"] * BF16            # v, o, do or dv
    row = T * m["H"] * F32
    return {"flash_fwd": 2 * qk + 2 * v + row,
            "flash_dq": 3 * qk + 2 * v + 2 * row,
            "flash_dkv": 3 * qk + 3 * v + 2 * row}


def grades_bytes_per_step(cell) -> Dict[str, int]:
    """Least HBM traffic of the freeze machinery's kernels in one step, over
    live rows only: ``grades_norm`` reads the f32 gradient and the bf16
    previous gradient and writes the latter (8 bytes a weight);
    ``masked_adamw`` reads the f32 weight, gradient and both moments and
    writes the weight and moments (28 bytes a weight)."""
    live = _live_rows(cell)
    n = sum(w * live[g] for g, w, _ in _matrices(cell))
    return {"grades_norm": 8 * n, "masked_adamw": 28 * n}


def frozen_share(cell) -> float:
    """Share of the monitored weights frozen at set-up."""
    live = _live_rows(cell)
    total = sum(w * rows for _, w, rows in _matrices(cell))
    return 1 - sum(w * live[g] for g, w, _ in _matrices(cell)) / total


def expert_flops_per_pick(cell) -> int:
    """FLOPs of one pass of a held expert's three matrices for one token's
    pick of it: ``2 * 3 * d * f``."""
    m = _dims(cell.config)
    return 6 * m["d"] * m["f"]
