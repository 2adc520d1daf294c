"""Config dataclasses shared by the whole framework.

Everything is a frozen dataclass so configs are hashable and usable as jit
static arguments. `ModelConfig` covers all 10 assigned LM-family archs via
feature flags; `GNNConfig` covers the paper's own models.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# LM-family model config
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention flavor -------------------------------------------------
    attention: str = "full"          # full | sliding | mixed | none
    window: int = 1024               # sliding-window size (mixed/sliding)
    global_every: int = 6            # in "mixed": every Nth layer is global
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    mrope: bool = False              # 3-axis multimodal RoPE (qwen2-vl)
    mrope_sections: Tuple[int, ...] = (16, 24, 24)   # t/h/w split of head_dim/2

    # --- MoE ---------------------------------------------------------------
    moe: bool = False
    num_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0
    shared_d_ff: int = 0             # qwen2-moe shared expert
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.001

    # --- SSM / RWKV ---------------------------------------------------------
    ssm_state: int = 0               # mamba-style state size (hymba)
    rwkv: bool = False               # attention-free RWKV6 token mixing
    hybrid: bool = False             # parallel attn + SSM heads (hymba)

    # --- encoder-decoder (whisper) -----------------------------------------
    encoder_decoder: bool = False
    num_encoder_layers: int = 0
    encoder_seq: int = 1500          # whisper: 30s @ 50Hz post-conv frames

    # --- VLM stub ------------------------------------------------------------
    vision_tokens: int = 0           # leading positions carrying patch embeds

    # --- misc ----------------------------------------------------------------
    norm_eps: float = 1e-6
    tie_embeddings: bool = False
    act: str = "silu"                # silu | gelu
    norm: str = "rmsnorm"            # rmsnorm | layernorm (whisper)
    mlp_bias: bool = False           # whisper uses biased linears
    learned_pos: bool = False        # whisper decoder positions
    logit_softcap: float = 0.0       # gemma-style tanh soft-capping (unused=0)
    dtype: str = "bfloat16"          # compute dtype

    # -----------------------------------------------------------------------
    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 256 so TP-16/32 sharding divides."""
        return _round_up(self.vocab_size, 256)

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def is_global_layer(self, i: int) -> bool:
        if self.attention == "full":
            return True
        if self.attention == "sliding":
            return False
        # "mixed": gemma3 pattern — every `global_every`-th layer is global
        return (i % self.global_every) == (self.global_every - 1)

    def scaled(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Tiny same-family variant for CPU smoke tests."""
        heads = min(self.num_heads, 4)
        kv = max(1, min(self.num_kv_heads, heads))
        # keep GQA ratio flavor: if original had kv < heads, keep kv < heads
        if self.num_kv_heads < self.num_heads:
            kv = max(1, heads // 2)
        kw = dict(
            name=self.name + "-smoke",
            num_layers=min(self.num_layers, 4),
            d_model=64,
            num_heads=heads,
            num_kv_heads=kv,
            head_dim=16,
            d_ff=128,
            vocab_size=512,
            window=16,
            global_every=2,
            encoder_seq=24,
        )
        if self.moe:
            kw.update(num_experts=min(self.num_experts, 8),
                      top_k=min(self.top_k, 2), moe_d_ff=32,
                      shared_d_ff=64 if self.shared_d_ff else 0)
        if self.num_encoder_layers:
            kw.update(num_encoder_layers=2)
        if self.ssm_state:
            kw.update(ssm_state=4)
        if self.vision_tokens:
            kw.update(vision_tokens=8)
        if self.mrope:
            kw.update(mrope_sections=(2, 3, 3))   # half of head_dim 16
        return self.scaled(**kw)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}


def long_context_ok(cfg: ModelConfig) -> bool:
    """long_500k runs only for sub-quadratic archs (see DESIGN.md §5)."""
    return cfg.rwkv or cfg.hybrid or cfg.attention in ("sliding", "mixed")


# ---------------------------------------------------------------------------
# GNN config (the paper's own models)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class GNNConfig:
    name: str
    model: str = "sage"              # sage | gcn | gat | gat_res
    num_layers: int = 3
    hidden_dim: int = 256
    in_dim: int = 602
    num_classes: int = 41
    fanout: Tuple[int, ...] = (10, 10, 10)
    gat_heads: int = 4
    dropout: float = 0.5
    dtype: str = "float32"
    # aggregation backend: "auto" picks the fused repro.kernels.gather_agg
    # Pallas kernel on TPU and the jnp reference elsewhere; "pallas" forces
    # the kernel (interpret-mode simulator off-TPU — validation only)
    agg_impl: str = "auto"           # auto | jnp | pallas


# ---------------------------------------------------------------------------
# COMM-RAND policy knobs (the paper's contribution, §4)
#
# DEPRECATED import location: `CommRandPolicy` lives in
# `repro.batching.policy` now, registered alongside the other batch
# policies ("rand" / "norand" / "comm_rand" / "clustergcn" / "labor").
# This re-export is a shim for existing callers.
# ---------------------------------------------------------------------------
from repro.batching.policy import CommRandPolicy  # noqa: E402,F401

BASELINE_POLICY = CommRandPolicy("rand", 0.0, 0.5)
NORAND_POLICY = CommRandPolicy("norand", 0.0, 1.0)
BEST_POLICY = CommRandPolicy("comm_rand", 0.125, 1.0)   # paper §6.1.3


# ---------------------------------------------------------------------------
# Training hyper-params (paper §5 defaults)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class TrainConfig:
    batch_size: int = 1024
    learning_rate: float = 1e-3
    weight_decay: float = 5e-4
    max_epochs: int = 100
    early_stop_patience: int = 6
    plateau_patience: int = 3
    plateau_factor: float = 0.1
    seed: int = 0
    # LM trainer extras
    grad_clip: float = 1.0
    microbatches: int = 1
    remat: bool = True
    grad_compression: bool = False


# ---------------------------------------------------------------------------
# Mesh config
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD = MeshConfig((16, 16), ("data", "model"))
MULTI_POD = MeshConfig((2, 16, 16), ("pod", "data", "model"))
