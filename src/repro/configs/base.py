"""Config system: model architecture configs + input-shape registry.

Every assigned architecture is a `ModelConfig` instance in its own module
(``src/repro/configs/<id>.py``).  A config fully determines the model: the
builder in ``repro.models.lm`` consumes nothing else.

Layer stacking is expressed as a repeating *period* of block descriptors
(``BlockDesc``) so that heterogeneous stacks (Jamba's 1:7 attn:mamba
interleave, xLSTM's mLSTM/sLSTM mix) scan cleanly: parameters are stacked
along a leading ``n_periods`` axis and the model body is a single
``lax.scan`` over periods, keeping the HLO small and compile times sane.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Sequence


# ---------------------------------------------------------------------------
# Block descriptors
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BlockDesc:
    """One entry of the repeating layer period."""

    kind: str           # "attn" | "mamba" | "mlstm" | "slstm"
    mlp: str = "dense"  # "dense" | "moe" | "none"

    def __post_init__(self):
        assert self.kind in ("attn", "mamba", "mlstm", "slstm"), self.kind
        assert self.mlp in ("dense", "moe", "none"), self.mlp


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # dense | moe | ssm | vlm | audio | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int                      # dense-MLP hidden size (0 = no dense MLP)
    vocab_size: int

    # --- attention details ---
    head_dim: int = 0              # 0 -> d_model // n_heads
    rope: str = "1d"               # "1d" | "2d" (chatglm partial) | "none"
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    norm: str = "rmsnorm"          # "rmsnorm" | "layernorm"
    act: str = "silu"              # "silu" (gated) | "gelu" (plain)
    tie_embeddings: bool = False

    # --- MoE ---
    n_experts: int = 0             # routed experts (the router's width)
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0              # per-expert hidden size
    n_dense_layers: int = 0        # leading dense layers before the period
    expert_offset: int = 0         # first routed expert held here
    n_held_experts: int = 0        # routed experts held here (0 = all)
    n_group: int = 1               # routing groups (group-limited greedy)
    topk_group: int = 1            # groups a token may route into
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True    # renormalise the top-k gates to sum 1

    # --- MLA (DeepSeek-V2) ---
    mla: bool = False
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling of the rope slice (factor 0 = none); the softmax
    # scale gains mscale(factor, yarn_mscale)^2
    yarn_factor: float = 0.0
    yarn_original_ctx: int = 4096
    yarn_mscale: float = 0.0

    # --- SSM (Mamba-style, SSD formulation) ---
    ssm_state_dim: int = 128
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv_width: int = 4
    ssm_chunk: int = 256           # chunkwise-parallel scan chunk (tunable site)

    # --- xLSTM ---
    xlstm_proj_factor: float = 2.0

    # --- encoder/decoder ---
    enc_dec: bool = False
    n_enc_layers: int = 0
    n_dec_layers: int = 0

    # --- modality frontend (STUB: input_specs provides embeddings) ---
    frontend: str = "none"         # "none" | "vision" | "audio"
    n_frontend_tokens: int = 0     # patches / frames occupying the prefix

    # --- layer period (heterogeneous stacks) ---
    period: tuple = (BlockDesc("attn", "dense"),)

    # --- numerics ---
    dtype: str = "bfloat16"

    # --- notes recorded into DESIGN/EXPERIMENTS ---
    source: str = ""
    notes: str = ""

    # ------------------------------------------------------------------
    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if not self.enc_dec:
            assert (self.n_layers - self.n_dense_layers) % len(self.period) \
                == 0, (f"{self.name}: n_layers={self.n_layers} less "
                       f"{self.n_dense_layers} dense not divisible by "
                       f"period of {len(self.period)}")
        if self.n_experts:
            assert self.n_experts % self.n_group == 0, self.n_group
            assert self.expert_offset + self.held_experts <= self.n_experts

    # ------------------------------------------------------------------
    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.n_dense_layers) // len(self.period)

    @property
    def dense_block(self) -> BlockDesc:
        """The leading dense layers' block: the period's mixer, dense MLP."""
        return BlockDesc(self.period[0].kind, "dense")

    @property
    def held_experts(self) -> int:
        return self.n_held_experts or self.n_experts

    @property
    def d_head_total(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def d_kv_total(self) -> int:
        return self.n_kv_heads * self.head_dim

    @property
    def d_inner_ssm(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def n_ssm_heads(self) -> int:
        return self.d_inner_ssm // self.ssm_head_dim

    @property
    def attention_free(self) -> bool:
        return all(b.kind != "attn" for b in self.period)

    @property
    def subquadratic(self) -> bool:
        """True when decode state does not grow quadratically costly with
        context — i.e. the arch may run the 500k-context shape."""
        return self.family in ("ssm", "hybrid")

    # ------------------------------------------------------------------
    def param_count(self) -> int:
        """Total parameter count (for 6·N·D roofline bookkeeping)."""
        return _count_params(self)

    def active_param_count(self) -> int:
        """Parameters active per token (MoE: shared + top-k routed)."""
        return _count_params(self, active_only=True)

    def reduced(self, **overrides) -> "ModelConfig":
        """A tiny same-family config for CPU smoke tests."""
        small = dict(
            n_layers=min(self.n_dense_layers, 1) + len(self.period),
            n_dense_layers=min(self.n_dense_layers, 1),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 4) if self.n_kv_heads > 1 else 1,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            head_dim=16,
            n_experts=min(self.n_experts, 4),
            moe_top_k=min(self.moe_top_k, 2),
            n_shared_experts=min(self.n_shared_experts, 1),
            moe_d_ff=64 if self.moe_d_ff else 0,
            expert_offset=0,
            n_held_experts=0,
            n_group=min(self.n_group, 2),
            topk_group=1,
            kv_lora_rank=32 if self.mla else 0,
            q_lora_rank=48 if (self.mla and self.q_lora_rank) else 0,
            qk_nope_dim=16 if self.mla else 0,
            qk_rope_dim=8 if self.mla else 0,
            v_head_dim=16 if self.mla else 0,
            ssm_state_dim=16,
            ssm_head_dim=16,
            ssm_chunk=8,
            n_enc_layers=2 if self.enc_dec else 0,
            n_dec_layers=2 if self.enc_dec else 0,
            n_frontend_tokens=8 if self.frontend != "none" else 0,
            dtype="float32",
            name=self.name + "-smoke",
        )
        if self.enc_dec:
            small["n_layers"] = 4
        small.update(overrides)
        return dataclasses.replace(self, **small)


def _gated(act: str) -> bool:
    return act == "silu"


def _count_params(c: ModelConfig, active_only: bool = False) -> int:
    d = c.d_model
    total = c.vocab_size * d                       # embed
    if not c.tie_embeddings:
        total += c.vocab_size * d                  # lm head

    def attn_params() -> int:
        if c.mla:
            p = 0
            q_dim = c.n_heads * (c.qk_nope_dim + c.qk_rope_dim)
            if c.q_lora_rank:
                p += d * c.q_lora_rank + c.q_lora_rank * q_dim
            else:
                p += d * q_dim
            p += d * (c.kv_lora_rank + c.qk_rope_dim)            # down (kv + rope)
            p += c.kv_lora_rank * c.n_heads * (c.qk_nope_dim + c.v_head_dim)
            p += c.n_heads * c.v_head_dim * d                    # out proj
            return p
        return d * c.d_head_total + 2 * d * c.d_kv_total + c.d_head_total * d

    def dense_mlp_params() -> int:
        mult = 3 if _gated(c.act) else 2
        return mult * d * c.d_ff

    def moe_mlp_params(active: bool) -> int:
        mult = 3 if _gated(c.act) else 2
        n_routed = c.moe_top_k if active else c.held_experts
        p = (n_routed + c.n_shared_experts) * mult * d * c.moe_d_ff
        p += d * c.n_experts                                      # router
        return p

    def ssm_params() -> int:
        di, n = c.d_inner_ssm, c.ssm_state_dim
        h = c.n_ssm_heads
        return (d * 2 * di + di * c.ssm_conv_width + di * 2 * n
                + di + h + di * d)

    def xlstm_params(kind: str) -> int:
        if kind == "mlstm":
            # up(2 branches) + block-diagonal per-head qkv + gates + down
            di = int(c.xlstm_proj_factor * d)
            return d * 2 * di + 3 * di * di // c.n_heads + 2 * di + di * d
        # sLSTM: 4 gates (input + block-diag recurrent per head) + GLU MLP
        hd = d // c.n_heads
        return 4 * d * d + 4 * c.n_heads * hd * hd + 2 * d * (4 * d // 3)

    def block_params(b: BlockDesc, active: bool) -> int:
        p = 0
        if b.kind == "attn":
            p += attn_params()
        elif b.kind == "mamba":
            p += ssm_params()
        elif b.kind in ("mlstm", "slstm"):
            p += xlstm_params(b.kind)
        if b.mlp == "dense":
            p += dense_mlp_params()
        elif b.mlp == "moe":
            p += moe_mlp_params(active)
        return p

    n_units = ((c.n_enc_layers + c.n_dec_layers) if c.enc_dec
               else c.n_layers - c.n_dense_layers)
    per_period = sum(block_params(b, active_only) for b in c.period)
    total += per_period * (n_units // len(c.period))
    total += c.n_dense_layers * block_params(c.dense_block, active_only)
    if c.enc_dec:   # cross-attention in decoder layers
        total += c.n_dec_layers * attn_params()
    return int(total)


# ---------------------------------------------------------------------------
# Input shapes
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES = {
    "train_4k":    ShapeConfig("train_4k",    4_096,   256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768,  32,  "prefill"),
    "decode_32k":  ShapeConfig("decode_32k",  32_768,  128, "decode"),
    "long_500k":   ShapeConfig("long_500k",   524_288, 1,   "decode"),
}


def supported_shapes(cfg: ModelConfig) -> dict:
    """Which of the four assigned shapes an arch runs; skips are recorded
    (DESIGN.md §Arch-applicability)."""
    out = {}
    for name, s in SHAPES.items():
        if name == "long_500k" and not cfg.subquadratic:
            out[name] = "SKIP: pure full-attention arch — 500k dense decode "\
                        "is quadratic-state; run only for ssm/hybrid per spec"
            continue
        out[name] = "run"
    return out


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

ARCH_IDS = (
    "starcoder2_7b",
    "qwen3_8b",
    "stablelm_3b",
    "chatglm3_6b",
    "deepseek_v2_236b",
    "llama4_maverick_400b",
    "xlstm_1_3b",
    "phi3_vision_4_2b",
    "seamless_m4t_medium",
    "jamba_v0_1_52b",
)


def get_config(arch: str) -> ModelConfig:
    import importlib
    arch = arch.replace("-", "_").replace(".", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def all_configs() -> dict:
    return {a: get_config(a) for a in ARCH_IDS}
