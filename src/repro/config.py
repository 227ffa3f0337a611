"""Central configuration system.

Everything in the framework is driven by three frozen dataclasses:

* :class:`ModelConfig`   — architecture hyperparameters (one per ``--arch``).
* :class:`GradESConfig`  — the paper's technique (threshold, grace period, monitor mode).
* :class:`TrainConfig`   — optimization / batching / checkpointing / mesh knobs.

Configs are plain data: hashable, serializable to/from JSON, comparable.  The
``repro/configs/<arch>.py`` modules each export ``CONFIG`` (the full published
architecture) and ``reduced()`` (a tiny same-family config for CPU smoke tests).
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Mapping, Optional, Tuple

from repro.robustness.faults import FaultPlan

# ---------------------------------------------------------------------------
# Model architecture
# ---------------------------------------------------------------------------

#: Families understood by the model zoo dispatcher (repro/models/model.py).
FAMILIES = ("dense", "moe", "encdec", "hybrid", "xlstm")


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts block settings, token-choice top-k.

    ``scoring="softmax"`` is the GShard layer (``models/moe.py::moe_block``):
    softmax top-k, capacity drops, aux and z losses.  ``scoring="sigmoid"``
    is DeepSeek-V3's (``moe.held_expert_block``): experts are selected by
    sigmoid score plus a per-expert correction bias (the ``router_bias``
    buffer, which nothing trains) and weighted by the unbiased scores of the
    chosen ones, normalised over them when ``norm_topk`` and scaled by
    ``routed_scale``; no token is dropped and no aux loss is added.  That
    layer holds experts ``[held_offset, held_offset + n_held)`` of the router's
    ``n_experts`` (one chip's share under expert parallelism; ``n_held`` 0 =
    all) and adds ``shared_d_ff``-wide shared experts for every token.
    """

    n_experts: int = 8             # the router's width
    top_k: int = 2
    d_ff: int = 0                  # per-expert hidden size
    capacity_factor: float = 1.25
    router_z_loss: float = 1e-3
    aux_loss: float = 1e-2
    group_size: int = 1024         # tokens per dispatch group (bounds scatter size)
    scoring: str = "softmax"       # "softmax" | "sigmoid"
    norm_topk: bool = True
    routed_scale: float = 1.0
    shared_d_ff: int = 0           # the shared experts as one SwiGLU; 0 = none
    n_held: int = 0
    held_offset: int = 0

    @property
    def held(self) -> int:
        """Experts whose weights this layer holds."""
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class SSMConfig:
    """Selective-SSM (Mamba-style) head settings for hybrid blocks."""

    state_dim: int = 16
    expand: int = 2                # inner dim = expand * d_model
    dt_rank: int = 0               # 0 -> ceil(d_model / 16)
    conv_width: int = 4


@dataclass(frozen=True)
class ModelConfig:
    name: str = "tiny"
    family: str = "dense"
    n_layers: int = 2
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 512
    vocab: int = 512
    head_dim: int = 0              # 0 -> d_model // n_heads
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    swa_window: int = 0            # 0 -> full causal attention
    tie_embeddings: bool = False
    mlp_act: str = "swiglu"        # "swiglu" | "gelu"
    # --- encoder/decoder (whisper) ---
    n_encoder_layers: int = 0
    n_frames: int = 1500           # audio frame stub length fed to the encoder
    # --- MoE ---
    moe: Optional[MoEConfig] = None
    # --- latent attention (MLA, DeepSeek-V2/V3) when kv_lora_rank > 0: q is
    # x·W_q with heads of qk_nope + qk_rope dims; keys and values come from a
    # kv_lora_rank-wide latent (RMSNorm'd) plus one shared rope head ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- leading dense layers (DeepSeek-V3's first_k_dense_replace): SwiGLU
    # layers of width dense_d_ff before the stacked scan; ``n_layers`` counts
    # the scan's layers (``params["layers"]``, what the GradES planner
    # segments), so the model has n_dense_layers + n_layers ---
    n_dense_layers: int = 0
    dense_d_ff: int = 0
    # --- hybrid (hymba): parallel attention + mamba heads ---
    ssm: Optional[SSMConfig] = None
    # --- xLSTM: ratio of mLSTM:sLSTM blocks handled by the xlstm stack ---
    # dtypes
    dtype: str = "bfloat16"        # activations / params compute dtype
    param_dtype: str = "float32"   # master parameter dtype
    # long-context capability flag (sub-quadratic attention path available)
    subquadratic: bool = False
    # sequence-parallel attention (Megatron-SP style): shard the seq dim over the
    # "model" axis inside attention blocks when head counts don't divide the TP
    # axis (EXPERIMENTS.md §Perf iteration 1).
    seq_parallel_attn: bool = False
    # --- attention dispatch (models/attention.py; DESIGN.md §3b) ---
    # jnp-fallback switch from full to blockwise attention (was hard-coded at
    # the attention() call sites).
    attn_chunk_threshold: int = 8192
    # attention-only backend override: "" inherits TrainConfig.kernels (so the
    # launcher's --kernels controls attention too); else "pallas"|"jnp"|"auto".
    attn_backend: str = ""

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def resolved_head_dim(self) -> int:
        if self.mla:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.head_dim or self.d_model // self.n_heads

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.resolved_head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.resolved_head_dim

    @property
    def attn_out_dim(self) -> int:
        """Width of the attention output that W_o maps back to d_model."""
        if self.mla:
            return self.n_heads * self.v_head_dim
        return self.q_dim

    @property
    def dt_rank(self) -> int:
        assert self.ssm is not None
        return self.ssm.dt_rank or max(1, -(-self.d_model // 16))

    def param_count(self) -> int:
        """Analytic parameter count (used for MODEL_FLOPS in the roofline)."""
        return _param_count(self, active_only=False)

    def active_param_count(self) -> int:
        """Params touched per token (MoE: shared + top_k experts)."""
        return _param_count(self, active_only=True)

    def monitored_param_count(self) -> int:
        """Params in the GradES-monitored per-layer matrices (attn + MLP
        projections + stacked SSM matrices for hybrids — everything
        ``core.grades._is_monitored`` picks up) — the pool whose dW FLOPs the
        Tier-1.5 segment plan can eliminate (roofline §8 frozen-fraction
        accounting).  Active-expert counting matches
        ``active_param_count``'s FLOP convention."""
        d = self.d_model
        attn = _attn_params(self)
        if self.moe is not None:
            mlp = 3 * d * self.moe.d_ff * self.moe.top_k \
                + d * self.moe.n_experts  # router is monitored too
            mlp += 3 * d * self.moe.shared_d_ff
        elif self.family == "xlstm":
            mlp = 2 * d * max(self.d_ff, 2 * d)
        else:
            mlp = (3 if self.mlp_act == "swiglu" else 2) * d * self.d_ff
        ssm = 0
        if self.ssm is not None:
            # every stacked (L, ...) ndim>=3 ssm matrix except the 2-d skip
            di = self.ssm.expand * d
            ssm = (d * 2 * di + di * (self.dt_rank + 2 * self.ssm.state_dim)
                   + self.dt_rank * di + di * self.ssm.state_dim + di * d
                   + di * self.ssm.conv_width)
        dense = self.n_dense_layers * (attn + 3 * d * self.dense_d_ff)
        return self.n_layers * (attn + mlp + ssm) + dense

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2)


def _attn_params(cfg: ModelConfig) -> int:
    """Weights of one layer's attention projections."""
    d = cfg.d_model
    if cfg.mla:
        r, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        return (d * cfg.q_dim + d * (r + dr)
                + r * cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                + cfg.attn_out_dim * d)
    return d * cfg.q_dim + 2 * d * cfg.kv_dim + cfg.q_dim * d


def _param_count(cfg: ModelConfig, *, active_only: bool) -> int:
    d = cfg.d_model
    attn = _attn_params(cfg)
    if cfg.moe is not None:
        e = cfg.moe.top_k if active_only else cfg.moe.held
        mlp = 3 * d * cfg.moe.d_ff * e + d * cfg.moe.n_experts  # experts + router
        mlp += 3 * d * cfg.moe.shared_d_ff
    elif cfg.family == "xlstm":
        mlp = 2 * d * max(cfg.d_ff, 2 * d)  # up/down proj around the recurrent core
    else:
        n_mats = 3 if cfg.mlp_act == "swiglu" else 2
        mlp = n_mats * d * cfg.d_ff
    norms = 2 * d + cfg.kv_lora_rank
    per_layer = attn + mlp + norms
    if cfg.ssm is not None:
        di = cfg.ssm.expand * d
        per_layer += d * 2 * di + di * (cfg.dt_rank + 2 * cfg.ssm.state_dim)
        per_layer += cfg.dt_rank * di + di * cfg.ssm.state_dim + di + di * d
        per_layer += di * cfg.ssm.conv_width
    if cfg.family == "xlstm":
        # q/k/v/o for mLSTM + gate projections; folded into attn above approximately.
        pass
    total = cfg.n_layers * per_layer
    total += cfg.n_dense_layers * (attn + 3 * d * cfg.dense_d_ff + norms)
    if cfg.n_encoder_layers:
        enc_per_layer = attn + 2 * d * cfg.d_ff + 2 * d          # gelu mlp
        dec_cross = d * cfg.q_dim + 2 * d * cfg.kv_dim + cfg.q_dim * d + d
        total += cfg.n_encoder_layers * enc_per_layer + cfg.n_layers * dec_cross
    total += cfg.vocab * d * (1 if cfg.tie_embeddings else 2) + d
    return total


# ---------------------------------------------------------------------------
# GradES
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class GradESConfig:
    """The paper's technique. ``tau`` / ``alpha`` follow Algorithm 1."""

    enabled: bool = True
    tau: float = 1e-3
    alpha: float = 0.5                   # grace-period fraction of total steps
    monitor: str = "delta"               # "delta" (Eq.1, stores prev grads) | "norm_delta"
    patience: int = 1                    # beyond-paper: consecutive sub-tau steps required
    # Per-component tau overrides, keyed by matrix-type name (paper Table 10 uses
    # modality-specific thresholds; we generalize to per-type).
    tau_overrides: Mapping[str, float] = field(default_factory=dict)
    # Normalize the L1 norm by element count (makes tau transferable across sizes).
    normalize: bool = True

    def tau_for(self, key: str) -> float:
        return dict(self.tau_overrides).get(key, self.tau)


# ---------------------------------------------------------------------------
# Training / runtime
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LoRAConfig:
    rank: int = 32
    alpha: float = 64.0
    targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class TrainConfig:
    seq_len: int = 1024
    global_batch: int = 8
    microbatch: int = 0                  # 0 -> no gradient accumulation
    steps: int = 100
    # optimizer
    optimizer: str = "adamw"             # "adamw" | "sgd"
    lr: float = 2e-5
    warmup_frac: float = 0.05
    schedule: str = "cosine"             # "cosine" | "constant"
    weight_decay: float = 0.01
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    opt_state_dtype: str = "float32"     # "bfloat16" for 1T-scale configs
    # kernel backend for the fused GradES monitor + masked-update hot path:
    # "pallas" forces the fused kernels (interpret mode off-TPU; warns once
    # and falls back per leaf on layouts the shard mapper can't take), "jnp"
    # forces the pure-XLA reference path, "auto" picks pallas on TPU — shard-
    # mapped over the active mesh when it has >1 device — and jnp elsewhere
    # (DESIGN.md §3).
    kernels: str = "auto"                # "pallas" | "jnp" | "auto"
    # --- sync-boundary trainer (DESIGN.md §4) ---
    # The host only wakes at block boundaries: the compiled step is lax.scan'd
    # over a stacked (sync_interval, ...) batch block with per-step metrics
    # kept on device, so per-step Python dispatch / device_get round-trips are
    # paid once per block.  1 reproduces per-step host behavior bit-exactly.
    # Tier-1 repartition checks run at boundaries aligned to
    # round_up(repartition_interval, sync_interval); two runs with different
    # sync_interval are bit-identical iff they resolve to the same aligned
    # interval — pick repartition_interval as a common multiple of the K
    # values being compared (e.g. 16 for K ∈ {1, 8, 16}).
    sync_interval: int = 1
    # Batch blocks ahead of the device that the background prefetch thread
    # keeps staged (sampled, stacked, device_put against the active mesh's
    # batch shardings).  0 disables the thread: blocks are built synchronously
    # on the training thread (debug / deterministic-ordering mode).
    prefetch_depth: int = 2
    # --- Tier 1.5: segmented layer scan (DESIGN.md §2) ---
    # Max segments the per-layer freeze plan may split the layer scan into;
    # also the boundary-quantization grid that bounds Tier-1.5 recompiles at
    # segment_max * n_types over a whole run (core/partition.py::segment_plan).
    # 1 degrades to the whole-type Tier-1 behavior (single monolithic scan).
    segment_max: int = 8
    # early stopping baselines
    grades: GradESConfig = field(default_factory=GradESConfig)
    lora: Optional[LoRAConfig] = None
    val_es: bool = False                 # classic validation early stopping
    val_interval_frac: float = 0.05
    val_patience: int = 3
    val_delta: float = 5e-4
    # memory / distribution
    remat: str = "none"                  # "none" | "full" | "dots"
    fsdp: bool = True                    # shard params over the data axis too
    grad_compression: str = "none"       # "none" | "int8_ef"
    # Freeze-aware explicit data-parallel gradient reduce (DESIGN.md §3;
    # distributed/reduce.py).  "auto" computes grads inside a shard_map that
    # is manual over the DP mesh axes and psums per-leaf under the boundary
    # ReducePlan — frozen leaves/rows drop out of the collective entirely —
    # whenever the active mesh is purely data-parallel; tensor-parallel
    # configs keep the implicit GSPMD reduce.  "explicit"
    # raises instead of falling back; "implicit" never engages.
    reduce_mode: str = "auto"            # "auto" | "explicit" | "implicit"
    # checkpointing.  NOTE: with GradES static repartition on, the Tier-1/1.5
    # freeze artifacts also refresh before each checkpoint (train/loop.py), so
    # checkpoint_every is part of the numeric schedule — runs are
    # bit-comparable only when their checkpoint boundaries coincide.
    checkpoint_dir: str = ""
    checkpoint_every: int = 0
    keep_checkpoints: int = 3
    seed: int = 0
    # --- robustness (DESIGN.md §4; robustness/) ---
    # All-finite sentinel fused into the per-block metrics; on a tripped block
    # the host rolls back to the last boundary snapshot, skips the offending
    # block, and backs off the LR by rollback_lr_backoff (multiplicative, per
    # rollback).  After max_rollbacks trips the run aborts with
    # stop_reason="nonfinite_abort" (EXIT_NONFINITE).
    numerics_guard: bool = True
    rollback_lr_backoff: float = 0.5
    max_rollbacks: int = 3
    # Straggler watchdog escalation: when > 0 and the drained per-step p95
    # exceeds this multiple of the healthy-EMA estimate, write a boundary
    # checkpoint and abort with stop_reason="straggler_abort" (EXIT_STRAGGLER)
    # so a supervisor can reschedule.  0 keeps today's log-only behavior.
    straggler_p95_abort: float = 0.0
    # Prefetcher: bounded retry with exponential backoff for transient batch-
    # read I/O errors, and a consumer-side stall timeout (seconds; 0 = block
    # forever) that raises PrefetchStalled instead of hanging on a wedged
    # worker.
    prefetch_retries: int = 3
    prefetch_retry_backoff: float = 0.05
    prefetch_stall_timeout: float = 0.0
    # Deterministic fault injection (tests / chaos lane only; None in prod).
    fault_plan: Optional[FaultPlan] = None


# ---------------------------------------------------------------------------
# Input shape cells (assigned shapes; every arch pairs with all four)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}


def shape_applicable(cfg: ModelConfig, shape: ShapeCell) -> Tuple[bool, str]:
    """Whether a (arch, shape) cell runs; see DESIGN.md §5b for the skip policy."""
    if shape.name == "long_500k" and not cfg.subquadratic:
        return False, "long_500k needs sub-quadratic attention; pure full-attention arch"
    return True, ""


def asdict(cfg: Any) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)
