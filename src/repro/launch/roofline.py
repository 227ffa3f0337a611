"""Roofline-term extraction from compiled dry-run artifacts (DESIGN.md §8).

    compute    = FLOPs/chip             / PEAK_FLOPS
    memory     = HBM bytes/chip         / HBM_BW
    collective = collective bytes/chip  / LINK_BW

``compiled.cost_analysis()`` counts every while-loop body ONCE (scanned layer
stacks would be undercounted 40–62×), so we walk the compiled, partitioned HLO text
ourselves:

* ``dot`` FLOPs = 2 · numel(result) · prod(lhs contracting dims), looked up from a
  per-computation symbol table;
* ``while`` recurses into the body × ``known_trip_count`` from backend_config
  (dynamic-trip loops — the causal kv-block loop — fall back to a per-cell
  estimate);
* ``fusion`` recurses into the called computation (FLOPs) but counts only its own
  result bytes (fusion internals never touch HBM);
* HBM traffic model: 2 × result bytes per materializing instruction (read+write
  amortized; pure-aliasing ops excluded);
* collective bytes: result-shape bytes of all-gather / all-reduce / reduce-scatter
  / all-to-all / collective-permute (post-SPMD => per-device), ring (n-1)/n factors
  ignored.

Everything is per-device because the walked module is the post-SPMD partition.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass
from typing import Any, Dict, List, Optional, Tuple

# v5e-class chip constants (per the assignment).
PEAK_FLOPS = 197e12        # bf16 FLOP/s per chip
HBM_BW = 819e9             # bytes/s per chip
LINK_BW = 50e9             # bytes/s per ICI link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-reduce.12 = f32[16,1024]{1,0} all-reduce(...)
#       ROOT %t = (bf16[8,128], bf16[8,128]) all-to-all(...)
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# ---------------------------------------------------------------------------
# HLO walker
# ---------------------------------------------------------------------------

_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\((.*)\)\s*->.*\{\s*$")
_PARAM_RE = re.compile(r"([\w.\-]+):\s*((?:\([^)]*\))|[a-z0-9]+\[[\d,]*\])")
_RESULT_SHAPE_RE = re.compile(r"^(\((?:[^()]|\([^)]*\))*\)|[a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?)")
_OP_RE = re.compile(r"([a-z][\w\-]*)\(")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"(?:calls|body)=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_ARGS_RE = re.compile(r"%([\w.\-]+)")

#: ops that neither compute nor move HBM bytes (aliasing / metadata).
_FREE_OPS = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast", "while",
    "conditional", "call", "after-all", "add-dependency", "reshape", "copy-done",
    "all-reduce-done", "all-gather-done", "custom-call",
})


class _Instr:
    __slots__ = ("name", "shape", "op", "rest")

    def __init__(self, name, shape, op, rest):
        self.name, self.shape, self.op, self.rest = name, shape, op, rest


def _parse_computations(txt: str) -> Tuple[Dict[str, List[_Instr]], str]:
    comps: Dict[str, List[_Instr]] = {}
    entry = ""
    cur: Optional[List[_Instr]] = None
    for line in txt.splitlines():
        s = line.strip()
        head = _COMP_HEAD_RE.match(s)
        if head and s.endswith("{"):
            cur = []
            comps[head.group(1)] = cur
            if line.startswith("ENTRY"):
                entry = head.group(1)
            for pname, pshape in _PARAM_RE.findall(head.group(2)):
                cur.append(_Instr(pname, pshape, "parameter", ""))
            continue
        if s == "}":
            cur = None
            continue
        if cur is None or "=" not in s:
            continue
        lhs, _, rest = s.partition(" = ")
        name = lhs.replace("ROOT", "").strip().lstrip("%")
        mshape = _RESULT_SHAPE_RE.match(rest)
        if not mshape:
            continue
        shape = mshape.group(1)
        tail = rest[mshape.end():]
        mop = _OP_RE.search(tail)
        if not mop:
            continue
        cur.append(_Instr(name, shape, mop.group(1), tail))
    return comps, entry


def _dot_flops(instr: _Instr, symtab: Dict[str, str]) -> float:
    out = 1
    for dt, dims in _SHAPE_RE.findall(instr.shape):
        for d in dims.split(","):
            if d:
                out *= int(d)
    cdims = _CDIMS_RE.search(instr.rest)
    k = 1
    args = _ARGS_RE.findall(instr.rest.split("),")[0])
    if cdims and args:
        lhs_shape = symtab.get(args[0], "")
        m = _SHAPE_RE.search(lhs_shape)
        if m:
            dims = [int(d) for d in m.group(2).split(",") if d]
            for ci in cdims.group(1).split(","):
                if ci and int(ci) < len(dims):
                    k *= dims[int(ci)]
    return 2.0 * out * k


def analyze_hlo(txt: str, *, default_dynamic_trip: float = 1.0) -> Dict[str, Any]:
    """Per-device (flops, hbm bytes, collective bytes) with loop-trip expansion."""
    comps, entry = _parse_computations(txt)
    memo: Dict[str, Tuple[float, float, float, Dict]] = {}

    def trip_of(instr: _Instr) -> float:
        m = _TRIP_RE.search(instr.rest)
        return float(m.group(1)) if m else float(default_dynamic_trip)

    def operand_bytes(i: _Instr, symtab) -> float:
        args_part = i.rest.split(")")[0]
        return float(sum(_shape_bytes(symtab.get(a, ""))
                         for a in _ARGS_RE.findall(args_part)))

    def _leading_dim(shape_str: str) -> int:
        m = _SHAPE_RE.search(shape_str)
        if not m or not m.group(2):
            return 0
        return int(m.group(2).split(",")[0])

    def instr_traffic(i: _Instr, symtab, trips: float) -> float:
        """HBM bytes for one instruction.

        * dynamic-update-slice (incl. fusions rooted in one) aliases its big
          buffer operand in place: real traffic is the update slice, not the
          buffer — charging the buffer per scan step invents O(T²) phantom
          bytes.  dynamic-slice likewise reads only the slice it produces.
        * Inside a while body with known trip count T, any operand whose
          leading dim == T is a stacked xs/saved-activation buffer accessed
          via per-step slicing: charge operand/T (the slice), not the stack.
        """
        res = _shape_bytes(i.shape)
        ops_ = []
        for a in _ARGS_RE.findall(i.rest.split(")")[0]):
            b = float(_shape_bytes(symtab.get(a, "")))
            if trips > 1 and _leading_dim(symtab.get(a, "")) == int(trips):
                b = b / trips
            ops_.append(b)
        total_ops = float(sum(ops_))
        name = i.name + i.op
        if "dynamic-update-slice" in name or "dynamic_update_slice" in name:
            big = max(ops_) if ops_ else 0.0
            return 2.0 * max(total_ops - big, 1.0)
        if i.op == "dynamic-slice" or "dynamic-slice" in i.name:
            return 2.0 * res
        if trips > 1 and _leading_dim(i.shape) == int(trips):
            res = res / trips  # stacked ys output written one slice per step
        return res + total_ops

    def walk(name: str, trips: float = 1.0) -> Tuple[float, float, float, Dict]:
        key = (name, trips)
        if key in memo:
            return memo[key]
        memo[key] = (0.0, 0.0, 0.0, {})  # cycle guard
        flops = mem = coll = 0.0
        per_kind: Dict[str, Dict[str, float]] = {}
        instrs = comps.get(name, [])
        symtab = {i.name: i.shape for i in instrs}
        for i in instrs:
            if i.op == "dot":
                flops += _dot_flops(i, symtab)
                mem += instr_traffic(i, symtab, trips)
            elif i.op == "while":
                t = trip_of(i)
                cm = _CALLS_RE.search(i.rest)
                if cm:
                    f2, m2, c2, pk2 = walk(cm.group(1), t)
                    flops += t * f2
                    mem += t * m2
                    coll += t * c2
                    for k, v in pk2.items():
                        slot = per_kind.setdefault(k, {"count": 0, "bytes": 0})
                        slot["count"] += t * v["count"]
                        slot["bytes"] += t * v["bytes"]
            elif i.op == "fusion":
                cm = _CALLS_RE.search(i.rest)
                if cm:
                    f2, _, c2, pk2 = walk(cm.group(1), 1.0)
                    flops += f2
                    coll += c2
                    for k, v in pk2.items():
                        slot = per_kind.setdefault(k, {"count": 0, "bytes": 0})
                        slot["count"] += v["count"]
                        slot["bytes"] += v["bytes"]
                mem += instr_traffic(i, symtab, trips)
            elif any(i.op.startswith(c) for c in _COLLECTIVES):
                b = _shape_bytes(i.shape)
                coll += b
                mem += 2.0 * b
                kind = next(c for c in _COLLECTIVES if i.op.startswith(c))
                slot = per_kind.setdefault(kind, {"count": 0, "bytes": 0})
                slot["count"] += 1
                slot["bytes"] += b
            elif i.op in _FREE_OPS:
                continue
            else:
                mem += instr_traffic(i, symtab, trips)
        memo[key] = (flops, mem, coll, per_kind)
        return memo[key]

    flops, mem, coll, per_kind = walk(entry)
    return {"flops": flops, "hbm_bytes": mem, "coll_bytes": coll,
            "per_kind": per_kind}


def collective_bytes(hlo_text: str) -> Tuple[int, Dict[str, Dict[str, float]]]:
    """Flat (no loop expansion) collective scan — kept for tests/backwards use."""
    per_kind: Dict[str, Dict[str, float]] = {}
    total = 0
    for m in _INSTR_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_str)
        total += b
        slot = per_kind.setdefault(kind, {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += b
    return total, per_kind


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes_per_chip: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float           # MODEL_FLOPS / HLO_FLOPs
    bytes_per_chip: float         # peak allocation from memory_analysis
    per_kind: Dict[str, Dict[str, float]]
    step_time_s: float = 0.0      # max of the three terms
    roofline_frac: float = 0.0    # dominant-term utilization proxy


def derive_terms(*, arch: str, shape: str, mesh_name: str, chips: int,
                 cost: Dict[str, float], hlo_text: str, model_flops: float,
                 bytes_per_chip: float,
                 default_dynamic_trip: float = 1.0) -> RooflineTerms:
    walked = analyze_hlo(hlo_text, default_dynamic_trip=default_dynamic_trip)
    flops = walked["flops"]            # per device
    byts = walked["hbm_bytes"]         # per device
    cbytes = walked["coll_bytes"]      # per device
    per_kind = walked["per_kind"]
    compute = flops / PEAK_FLOPS
    memory = byts / HBM_BW
    collective = cbytes / LINK_BW
    terms = {"compute": compute, "memory": memory, "collective": collective}
    bottleneck = max(terms, key=terms.get)
    step = max(terms.values())
    useful = (model_flops / chips) / flops if flops else 0.0
    # roofline fraction: useful model FLOPs per chip-second at the (dominant-term)
    # step time vs the chip's peak — the score we hillclimb.
    frac = (model_flops / chips / step) / PEAK_FLOPS if step > 0 else 0.0
    return RooflineTerms(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips, hlo_flops=flops,
        hlo_bytes=byts, coll_bytes_per_chip=float(cbytes), compute_s=compute,
        memory_s=memory, collective_s=collective, bottleneck=bottleneck,
        model_flops=model_flops, useful_ratio=useful,
        bytes_per_chip=bytes_per_chip, per_kind=per_kind, step_time_s=step,
        roofline_frac=frac)


def model_flops_for(cfg, cell, dw_skip_params: float = 0.0) -> float:
    """MODEL_FLOPS: 6·N·D train, 2·N·D decode/prefill (N = active params).

    ``dw_skip_params`` (train cells only) is the parameter count whose dW
    einsums the Tier-1.5 segment plan eliminates
    (``core.partition.plan_skipped_params``): the 6·N·D train budget is
    fwd 2·N·D + dX 2·N·D + dW 2·N·D, and a frozen (layer, type) row removes
    exactly its 2·params·tokens dW term — so modeled backward FLOPs fall
    linearly with the frozen fraction of the monitored pool
    (``cfg.monitored_param_count()``), per the GradES claim (DESIGN.md §8).
    """
    n = cfg.active_param_count()
    tokens = cell.global_batch * (1 if cell.kind == "decode" else cell.seq_len)
    mult = 6.0 if cell.kind == "train" else 2.0
    flops = mult * n * tokens
    if cell.kind == "train" and dw_skip_params:
        # ``plan_skipped_params`` counts *stored* rows; the 6·N·D budget uses
        # active-expert params, so cap the credit at the active monitored
        # pool — MoE stored-expert counts would otherwise over-subtract
        # (each expert row's realized dW is scaled by its top_k/E token
        # share, which the active-param convention already folds in).
        skip = min(float(dw_skip_params), float(cfg.monitored_param_count()))
        flops -= 2.0 * skip * tokens
    if cell.kind == "decode" and not cfg.subquadratic:
        # attention reads over the KV cache dominate decode; keep the matmul
        # convention (documented) — cache traffic shows up in the memory term.
        pass
    return flops


def grades_dw_curve(cfg, cell, fracs=(0.0, 0.25, 0.5, 0.75, 1.0)):
    """Modeled step-FLOP curve vs per-layer frozen fraction of the monitored
    matrices — the quantity the segmented layer scan (Tier 1.5) realizes;
    the chip benchmark (``bench/``) measures the step at a given freeze
    state."""
    pool = cfg.monitored_param_count()
    base = model_flops_for(cfg, cell)
    rows = []
    for f in fracs:
        flops = model_flops_for(cfg, cell, dw_skip_params=f * pool)
        rows.append({"frozen_frac": f, "model_flops": flops,
                     "dw_skip_params": f * pool,
                     "flop_speedup": base / flops if flops else 0.0})
    return rows


def reduce_bytes_model(n_params: float, frozen_params: float = 0.0,
                       compress: bool = False, dtype_bytes: float = 4.0
                       ) -> float:
    """Per-device bytes the data-parallel gradient reduce moves per step.

    Ring all-reduce moves ~2x the payload per device (reduce-scatter +
    all-gather legs); the freeze-aware explicit reduce (``distributed/
    reduce.py``) removes frozen parameters from the payload outright, and
    int8-EF compression (``distributed/compression.py``) carries 1 byte per
    surviving element on the wire instead of ``dtype_bytes`` (per-matrix fp32
    scales are O(leaves), negligible).  The measured counterpart is
    :func:`collective_bytes` over the compiled step's HLO."""
    live = max(float(n_params) - float(frozen_params), 0.0)
    wire = 1.0 if compress else float(dtype_bytes)
    return 2.0 * live * wire


def grades_collective_curve(cfg, fracs=(0.0, 0.25, 0.5, 0.75, 1.0),
                            dtype_bytes: float = 4.0):
    """Modeled reduce-bytes curve vs frozen fraction of the monitored pool,
    with and without int8 compression of the survivors — the collective-term
    analogue of :func:`grades_dw_curve`.  ``bytes_saving`` is vs the
    uncompressed full-tree reduce."""
    pool = cfg.monitored_param_count()
    total = cfg.param_count()
    base = reduce_bytes_model(total, dtype_bytes=dtype_bytes)
    rows = []
    for f in fracs:
        for compress in (False, True):
            b = reduce_bytes_model(total, f * pool, compress=compress,
                                   dtype_bytes=dtype_bytes)
            rows.append({"frozen_frac": f, "compress": compress,
                         "reduce_bytes": b,
                         "bytes_saving": base / b if b else float("inf")})
    return rows


def top_costs(txt: str, n: int = 20, *, default_dynamic_trip: float = 1.0):
    """Heaviest instructions by trip-expanded HBM bytes (for §Perf debugging)."""
    comps, entry = _parse_computations(txt)
    rows = []

    def trip_of(instr):
        m = _TRIP_RE.search(instr.rest)
        return float(m.group(1)) if m else float(default_dynamic_trip)

    def walk(name, mult):
        instrs = comps.get(name, [])
        symtab = {i.name: i.shape for i in instrs}
        for i in instrs:
            if i.op == "while":
                cm = _CALLS_RE.search(i.rest)
                if cm:
                    walk(cm.group(1), mult * trip_of(i))
            elif i.op in _FREE_OPS:
                continue
            else:
                args_part = i.rest.split(")")[0]
                ops_ = [_shape_bytes(symtab.get(a, ""))
                        for a in _ARGS_RE.findall(args_part)]
                name = i.name + i.op
                if "dynamic-update-slice" in name or i.op == "dynamic-slice" \
                        or "dynamic-slice" in i.name:
                    big = max(ops_) if ops_ else 0
                    b = 2.0 * max(sum(ops_) - big, 1.0) * mult
                else:
                    b = (_shape_bytes(i.shape) + sum(ops_)) * mult
                rows.append((b, i.op, i.name, i.shape[:60], mult))

    walk(entry, 1.0)
    rows.sort(reverse=True)
    return rows[:n]
