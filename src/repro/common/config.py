"""Shared configuration dataclasses for the framework.

A single ``ModelConfig`` covers every architecture family supported by the
framework (dense decoder LMs, MoE, SSM, hybrid, encoder-decoder audio, VLM,
and the paper's skeleton-GCN).  Family-specific fields default to "off".

Configs are frozen dataclasses so they can be hashed and closed over by
jit'd step functions without retracing hazards.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# The serve driver's --batch 0 family defaults, resolved in ONE place
# (ModelConfig.serve_batch) — subcommand code must never hardcode its own
# fallback, so `serve clip` / `serve stream` / legacy flag spellings can
# not skew apart.  Keyed "<family>:<mode>", with a global fallback.
SERVE_BATCH_DEFAULTS = {
    "gcn:clip": 8,       # batched two-stream clip inference
    "gcn:stream": 4,     # lockstep per-frame streaming
    "default": 4,        # LM families (decode batch)
}

# Forms of the 2s-AGCN data-dependent graph C_k (repro.core.agcn.adaptive):
# "window" pools θ/φ over a trailing window of frames, so live streams can
# compute it; "clip" is the published form, pooled over the whole clip.
CK_FORMS = ("window", "clip")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description.

    ``family`` selects the model builder:
      dense   — decoder-only transformer (GQA, optional SWA / local:global)
      moe     — decoder-only transformer with MoE FFN
      ssm     — xLSTM-style (mLSTM + sLSTM blocks)
      hybrid  — Mamba2 backbone + shared attention blocks (Zamba2)
      audio   — encoder-decoder transformer, stub conv frontend (Whisper)
      vlm     — decoder transformer consuming mixed text+patch embeddings
      gcn     — the paper's 2s-AGCN skeleton model
    """

    name: str
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                      # 0 -> d_model // num_heads

    # --- attention pattern ---
    window_size: int = 0                   # >0 -> sliding-window attention
    local_global_ratio: int = 0            # n -> n local layers per 1 global
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    act: str = "silu"                      # silu | gelu | relu2

    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    moe_capacity_factor: float = 1.25

    # --- SSM / hybrid ---
    ssm_state: int = 0                     # mamba2 state dim per head
    ssm_conv: int = 4                      # short conv width
    slstm_every: int = 0                   # xlstm: 1 sLSTM per this many blocks
    shared_attn_every: int = 0             # zamba2: shared attn block period
    ssm_expand: int = 2

    # --- encoder-decoder (audio) ---
    encoder_layers: int = 0
    encoder_frames: int = 1500             # whisper stub frontend output length

    # --- vlm ---
    num_image_tokens: int = 0              # patch embeddings per sample (stub)

    # --- gcn (2s-AGCN) ---
    gcn_joints: int = 25
    gcn_frames: int = 300
    gcn_persons: int = 2
    gcn_in_channels: int = 3
    gcn_num_classes: int = 60
    gcn_channels: Tuple[int, ...] = ()     # per-block output channels
    gcn_strides: Tuple[int, ...] = ()
    gcn_kv: int = 3                        # K_v neighbour subsets
    gcn_tkernel: int = 9                   # temporal kernel size
    use_ck: bool = False                   # data-dependent C_k graph
    ck_form: str = "window"                # C_k form (CK_FORMS): "window" =
                                           # trailing-window streaming form,
                                           # "clip" = published whole-clip

    # --- paper technique knobs (first-class features) ---
    prune_channel_fracs: Tuple[float, ...] = ()  # per-block kept fraction (C1)
    cavity_pattern: str = ""               # e.g. "cav-70-1" (C2)
    input_skip: int = 1                    # keep 1 of every `input_skip` frames
    rfc_bank: int = 16                     # RFC bank width (C3)
    rfc_minibank: int = 4                  # RFC mini-bank depth granularity
    gcn_stream_pool: int = 0               # streaming logit pool: 0 = running
                                           # mean over every emitted frame
                                           # (clip-parity contract); W > 0 =
                                           # sliding window of the last W
                                           # emitted frames (live streams
                                           # where the action changes)
    gcn_backend: str = "reference"         # engine backend: reference | pallas.
                                           # Default for eager forward() calls;
                                           # jitted steps (train/loss_fn) always
                                           # run the differentiable reference —
                                           # pallas rides prebuilt ExecutionPlans
                                           # (steps.make_gcn_infer_step, serve)

    # --- distribution hints ---
    scan_group: int = 1                    # layers per scan body group
    remat: str = "full"                    # full | dots | none
    sharding: str = "2d"                   # 2d (TP+FSDP) | dp_only (small
                                           # models: replicate weights, use
                                           # the model axis as extra DP)
    train_microbatches: int = 2            # grad-accum steps so activation
                                           # temp fits 16 GB/chip HBM

    def __post_init__(self):
        if self.ck_form not in CK_FORMS:
            raise ValueError(f"unknown ck_form {self.ck_form!r} "
                             f"(expected one of {CK_FORMS})")
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    def serve_batch(self, mode: str = "", requested: int = 0) -> int:
        """Resolve the serve driver's batch size in one place.

        ``requested`` (an explicit ``--batch N``) always wins; ``0`` falls
        back to the ``SERVE_BATCH_DEFAULTS`` entry for ``(family, mode)``
        — e.g. ``gcn:clip`` → 8, ``gcn:stream`` → 4 — then to the global
        default.  Every serve subcommand routes through here so defaults
        cannot skew across CLI spellings."""
        if requested:
            return requested
        return SERVE_BATCH_DEFAULTS.get(
            f"{self.family}:{mode}", SERVE_BATCH_DEFAULTS["default"])

    # ---- derived sizes ----
    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 256)

    @property
    def padded_experts(self) -> int:
        """Experts padded so the mesh model axis divides them (see DESIGN §5)."""
        if self.num_experts == 0:
            return 0
        return _round_up(self.num_experts, 16)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def param_count_estimate(self) -> int:
        """Analytic parameter count (used for 6·N·D model FLOPs)."""
        if self.family == "gcn":
            total = 0
            cin = self.gcn_in_channels
            for cout in self.gcn_channels:
                total += self.gcn_kv * cin * cout          # spatial 1x1 per subset
                total += cout * cout * self.gcn_tkernel    # temporal 9x1
                total += self.gcn_kv * self.gcn_joints**2  # B_k graphs
                cin = cout
            total += cin * self.gcn_num_classes
            return total
        d, L = self.d_model, self.num_layers
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.family == "moe":
            ffn = 3 * d * self.moe_d_ff * self.num_experts + d * self.num_experts
        elif self.family == "ssm":
            inner = self.ssm_expand * d
            ffn = 0
            attn = 2 * d * inner + inner * d + inner * d  # mLSTM projections (approx)
        elif self.family == "hybrid":
            inner = self.ssm_expand * d
            ffn = d * self.d_ff * 3 // max(1, self.shared_attn_every)
            attn = 2 * d * inner + inner * d
        else:
            ffn = 3 * d * self.d_ff if self.act in ("silu", "gelu") else 2 * d * self.d_ff
        emb = self.padded_vocab * d
        enc = 0
        if self.encoder_layers:
            enc = self.encoder_layers * (attn + 2 * d * self.d_ff)
        return L * (attn + ffn) + emb + enc

    def active_param_count_estimate(self) -> int:
        """Active params per token (MoE uses top-k experts only)."""
        if self.family != "moe":
            return self.param_count_estimate()
        d, L = self.d_model, self.num_layers
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        ffn = 3 * d * self.moe_d_ff * self.experts_per_token
        return L * (attn + ffn) + self.padded_vocab * d


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    """One input-shape cell (arch × shape makes a dry-run cell)."""

    name: str                # train_4k | prefill_32k | decode_32k | long_500k | gcn_*
    kind: str                # train | prefill | decode
    seq_len: int
    global_batch: int

    @property
    def is_decode(self) -> bool:
        return self.kind == "decode"


SHAPES = {
    "train_4k": ShapeConfig("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeConfig("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeConfig("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeConfig("long_500k", "decode", 524_288, 1),
}

# GCN (paper) shapes: batch of skeleton clips (N, C, T, V, M).
GCN_SHAPES = {
    "gcn_train": ShapeConfig("gcn_train", "train", 300, 512),
    "gcn_infer": ShapeConfig("gcn_infer", "prefill", 300, 2048),
}


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1_000
    weight_decay: float = 0.1
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip: float = 1.0
    microbatches: int = 1
    seed: int = 0
    checkpoint_every: int = 100
    checkpoint_dir: str = "/tmp/repro_ckpt"
    dtype: str = "bfloat16"
    grad_compression: str = "none"   # none | bf16 — compress the gradients
                                     # before the data-parallel sync (halves
                                     # DP collective bytes; moments stay f32)
