"""Configuration dataclasses for SEDAR-JAX.

Every run is described by a `RunConfig`, which composes:
  * `ModelConfig`   -- architecture hyper-parameters (one per assigned arch).
  * `MeshConfig`    -- device mesh shape / axis names.
  * `TrainConfig`   -- optimizer / schedule / batching.
  * `SedarConfig`   -- the paper's fault-tolerance knobs (protection level,
                       checkpoint interval, comparison mode, ...).

Configs are plain frozen dataclasses so they can be hashed into jit static
arguments and serialized into checkpoint manifests.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters.

    The same dataclass describes every family in the assigned pool; family-
    specific fields are zero / empty when unused.
    """

    name: str
    family: str                       # dense | moe | hybrid | vlm | audio | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                 # 0 -> d_model // num_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    mlp_act: str = "swiglu"           # swiglu | gelu

    # YaRN rope scaling as (key, value) pairs with the published keys
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim); () -> plain rope
    rope_scaling: Tuple[Tuple[str, float], ...] = ()

    # --- latent attention (MLA, DeepSeek-V2) -------------------------------
    kv_lora_rank: int = 0             # >0 -> MLA over a latent KV cache this wide
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0         # one rope key, shared by every head
    v_head_dim: int = 0

    # --- MoE ---------------------------------------------------------------
    num_experts: int = 0              # routed experts held here
    experts_per_token: int = 0
    router_experts: int = 0           # router width over every chip (0 -> num_experts)
    expert_offset: int = 0            # router index of the first expert held here
    moe_d_ff: int = 0                 # routed expert width (0 -> d_ff)
    shared_d_ff: int = 0              # shared experts, as one MLP this wide (0 -> none)
    moe_raw_topk: bool = False        # top-k weights as the softmax gives them (no renorm)
    first_dense_layers: int = 0       # leading layers with a dense MLP (moe family)
    dense_d_ff: int = 0               # their width (0 -> d_ff)

    # --- hybrid (recurrentgemma-style) --------------------------------------
    # Repeating block pattern, e.g. ("recurrent", "recurrent", "attention").
    block_pattern: Tuple[str, ...] = ()
    window_size: int = 0              # sliding-window size for local attention
    d_rnn: int = 0                    # RG-LRU recurrent width (0 -> d_model)
    conv_width: int = 4               # temporal-conv width in recurrent block

    # --- ssm / xlstm ---------------------------------------------------------
    # e.g. ("mlstm", "slstm") repeated; chunk size for the chunkwise form.
    mlstm_chunk: int = 256
    proj_factor: float = 2.0          # xLSTM up-projection factor

    # --- encoder-decoder ------------------------------------------------------
    encoder_layers: int = 0           # >0 -> enc-dec model (decoder = num_layers)
    cross_attention: bool = False

    # --- modality frontend (stub per task spec) ------------------------------
    frontend: Optional[str] = None    # "vision_stub" | "audio_stub" | None
    frontend_seq: int = 0             # length of precomputed embedding sequence
    frontend_dim: int = 0             # width of precomputed embeddings

    # --- numerics -------------------------------------------------------------
    dtype: str = "bfloat16"           # activation / compute dtype
    param_dtype: str = "float32"      # master parameter dtype

    # --- attention implementation --------------------------------------------
    attention_impl: str = "xla"       # "xla" (einsum, GSPMD-native) | "pallas"

    # --- remat ---------------------------------------------------------------
    # "full" (save nothing inside checkpointed bodies) is the production
    # default: with two-level scan remat the only persisted activations are
    # the seq-sharded residual-stream carries; "minimal"
    # (dots_with_no_batch_dims_saveable) pins the FSDP-gathered weights and
    # blows HBM at 100B scale (see EXPERIMENTS.md §Perf iteration log).
    remat: str = "full"               # none | minimal | full

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        if self.family == "hybrid" and self.d_rnn == 0:
            object.__setattr__(self, "d_rnn", self.d_model)

    # -- derived sizes ---------------------------------------------------------

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def rope_dim(self) -> int:
        """Width that rope rotates: the shared rope key under MLA."""
        return self.qk_rope_head_dim if self.kv_lora_rank else self.head_dim

    def param_count(self) -> int:
        """Total parameter count N (exact, mirrors the builders in models/)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self)

    def active_param_count(self) -> int:
        """Active-per-token parameters (MoE: only routed experts count)."""
        from repro.models.model import count_params_analytic

        return count_params_analytic(self, active_only=True)


# ---------------------------------------------------------------------------
# Mesh
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axis_names: Tuple[str, ...] = ("data", "model")

    @property
    def multi_pod(self) -> bool:
        return "pod" in self.axis_names

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


# ---------------------------------------------------------------------------
# Training / serving
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TrainConfig:
    global_batch: int = 256
    seq_len: int = 4096
    microbatch: int = 0               # 0 -> no gradient accumulation
    steps: int = 100
    optimizer: str = "adamw"          # adamw | sgdm | adafactor
    lr: float = 3e-4
    warmup_steps: int = 100
    schedule: str = "cosine"          # cosine | linear | constant
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    seed: int = 0
    # Distributed-optimization knobs
    grad_compression: str = "none"    # none | int8_ef  (cross-pod all-reduce)
    donate_state: bool = True


@dataclass(frozen=True)
class ServeConfig:
    batch: int = 128
    context_len: int = 32_768
    prefill_chunk: int = 0            # 0 -> single-shot prefill
    cache_dtype: str = "bfloat16"


# ---------------------------------------------------------------------------
# SEDAR (the paper's knobs)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SedarConfig:
    """Fault-tolerance configuration (paper Secs. 3.1-3.3).

    level:
      0 -- protection off (the paper's *baseline* is modeled separately as two
           independent instances + vote; see runtime/train.py --manual-vote).
      1 -- detection + notification + safe stop          (paper Sec. 3.1)
      2 -- multiple system-level checkpoints + rollback  (paper Sec. 3.2, Alg. 1)
      3 -- single validated application-level checkpoint (paper Sec. 3.3, Alg. 2)
    """

    level: int = 3
    # none | dual | sequential | fused (single-launch time redundancy,
    # DESIGN.md §11) | vote (N>=3, beyond paper) | abft | hybrid (replica-
    # free checksum detection, DESIGN.md §10; hybrid adds FSC fingerprint
    # checks)
    replication: str = "dual"
    replica_axis: str = "pod"         # mesh axis carrying replicas
    compare: str = "fingerprint"      # fingerprint | full   (full = paper's exact buffer compare)
    validate_interval: int = 1        # steps between gradient-fingerprint compares (TDC boundary)
    # deferred validation window D (DESIGN.md §11): commit predicates stay
    # on device and are read back every D compares. 1 = classic sync-per-
    # compare; >=8 makes the fault-free protected step host-sync-free at a
    # detection latency of <= D steps (requires a checkpointing level).
    validate_lag: int = 1
    param_validate_interval: int = 50 # steps between param/opt-state compares (FSC boundary)
    checkpoint_interval: int = 50     # steps between checkpoints (t_i analogue)
    checkpoint_dir: str = "/tmp/sedar_ckpt"
    max_checkpoints: int = 0          # L2 chain depth; 0 = unbounded (paper: none deleted)
    async_checkpoint: bool = True
    # -- tiered checkpoint hierarchy (DESIGN.md §12) -------------------------
    # comma-list of tiers (device | host | disk | partner); "disk" alone is
    # the classic flat store. device = on-device snapshot ring (instant
    # rollback, zero D2H/disk reads); host = host-RAM ring (one batched D2H,
    # no serialization); partner = redundant second directory with
    # independent digests (the Tier-2 corruption fallback).
    ckpt_tiers: str = "disk"
    device_ring_slots: int = 4        # Tier-0 ring capacity (versions)
    host_ring_slots: int = 4          # Tier-1 ring capacity (versions)
    device_ckpt_interval: int = 1     # Tier-0 cadence (steps; ~free)
    host_ckpt_interval: int = 0       # Tier-1 cadence; 0 -> checkpoint_interval
    partner_ckpt_interval: int = 0    # Tier-3 cadence; 0 -> checkpoint_interval
    ckpt_delta: bool = False          # L2 delta checkpoints (manifest leaf refs)
    ckpt_compress: bool = False       # np.savez_compressed leaf payloads
    toe_timeout_s: float = 120.0      # replica-heartbeat timeout (TOE detection)
    app_level_dtype: str = "float32"  # L3 payload dtype for params ("bfloat16" halves t_ca)
    fused_fingerprint: bool = True    # fuse fingerprint into the update step (beyond-paper opt)


# ---------------------------------------------------------------------------
# Top-level run
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    mesh: MeshConfig = field(default_factory=MeshConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)
    sedar: SedarConfig = field(default_factory=SedarConfig)

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Assigned input-shape sets (task spec: 4 shapes per LM arch)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES: Tuple[ShapeSpec, ...] = (
    ShapeSpec("train_4k",    "train",   4_096,   256),
    ShapeSpec("prefill_32k", "prefill", 32_768,  32),
    ShapeSpec("decode_32k",  "decode",  32_768,  128),
    ShapeSpec("long_500k",   "decode",  524_288, 1),
)

SHAPE_BY_NAME = {s.name: s for s in SHAPES}


def shape_applicable(model: ModelConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    """Task-spec applicability: ``long_500k`` only for sub-quadratic archs.

    Returns (applicable, reason_if_not).
    """
    if shape.name == "long_500k" and model.family not in ("hybrid", "ssm"):
        return False, (
            "long_500k skipped: pure full-attention architecture (dense 500k KV "
            "cache); per task spec only SSM/hybrid/linear-attention archs run it "
            "(see DESIGN.md)"
        )
    return True, ""


# ---------------------------------------------------------------------------
# Reduced configs for CPU smoke tests
# ---------------------------------------------------------------------------

def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """Scale an architecture down to CPU-smoke size, preserving its family
    structure (GQA ratio, MoE top-k, block pattern, enc-dec split, frontend)."""
    heads = max(2, min(cfg.num_heads, 4))
    kv = max(1, min(cfg.num_kv_heads, heads))
    # preserve GQA grouping: heads must be a multiple of kv heads
    heads = (heads // kv) * kv or kv
    head_dim = 16
    if cfg.family == "ssm":
        d_model = heads * head_dim      # xLSTM: inner dim == d_model
    else:
        d_model = heads * head_dim * 2  # up-projection headroom, divisible by heads
    pattern = cfg.block_pattern
    if pattern:
        layers = 2 * len(pattern)   # two full pattern groups
    elif cfg.family == "ssm":
        layers = 2
    else:
        layers = 2
    return dataclasses.replace(
        cfg,
        num_layers=layers,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=head_dim,
        d_ff=4 * d_model if cfg.d_ff else 0,
        vocab_size=257,              # deliberately non-multiple-of-2 vocab
        num_experts=min(cfg.num_experts, 4) if cfg.num_experts else 0,
        experts_per_token=min(cfg.experts_per_token, 2) if cfg.experts_per_token else 0,
        window_size=min(cfg.window_size, 8) if cfg.window_size else 0,
        d_rnn=d_model if cfg.family == "hybrid" else 0,
        encoder_layers=2 if cfg.encoder_layers else 0,
        frontend_seq=min(cfg.frontend_seq, 6) if cfg.frontend_seq else 0,
        frontend_dim=d_model if cfg.frontend_dim else 0,
        mlstm_chunk=8,
        dtype="float32",
        param_dtype="float32",
        remat="none",
    )
