"""Architecture configuration schema + the four assigned input shapes."""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace

import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class YarnScaling:
    """YaRN rotary scaling (arXiv:2309.00071) as DeepSeek-V2 configures it:
    frequencies past the correction range are divided by `factor`, those
    below it kept, a linear ramp between; the softmax scale gains
    mscale(factor, mscale_all_dim) squared."""
    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float

    def _mscale(self, m: float) -> float:
        if self.factor <= 1:
            return 1.0
        return 0.1 * m * math.log(self.factor) + 1.0

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """float32[dim / 2] rotary frequencies."""
        extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
        inter = extra / self.factor

        def corr(rot):
            turns = self.original_max_position / (rot * 2 * math.pi)
            return dim * math.log(turns) / (2 * math.log(theta))
        low = max(math.floor(corr(self.beta_fast)), 0)
        high = min(math.ceil(corr(self.beta_slow)), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)

    @property
    def cos_scale(self) -> float:
        return self._mscale(self.mscale) / self._mscale(self.mscale_all_dim)

    @property
    def softmax_gain(self) -> float:
        return self._mscale(self.mscale_all_dim) ** 2


@dataclass(frozen=True)
class ArchConfig:
    arch_id: str
    family: str                    # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None    # default d_model // n_heads

    # attention variants
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10_000.0
    rope_theta_global: float | None = None   # gemma3 global layers
    partial_rotary: float = 1.0
    sliding_window: int | None = None        # local window size
    local_global_ratio: int | None = None    # gemma3: 5 local : 1 global
    mrope_sections: tuple[int, int, int] | None = None
    tie_embeddings: bool = False
    embed_scale: bool = False                # gemma3 multiplies by sqrt(d)

    # multi-head latent attention (DeepSeek-V2); kv_lora_rank 0 = off
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_yarn: YarnScaling | None = None

    # MoE
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1             # 2 => MoE on every other layer (llama4)
    first_k_dense: int = 0         # leading dense layers ahead of the scan
    shared_expert: bool = False
    shared_expert_d_ff: int | None = None   # default d_ff
    expert_d_ff: int | None = None
    norm_topk_prob: bool = True    # renormalise the top-k gates to sum 1
    routed_scaling_factor: float = 1.0
    # experts [start, stop) of the n_experts that this chip holds: routing
    # runs over all n_experts, and the dropless grouped path computes the
    # held experts' share of the result.  None = the capacity paths over
    # all experts (moe_impl)
    experts_held: tuple[int, int] | None = None
    capacity_factor: float = 1.25
    # "einsum": GShard one-hot-matmul dispatch (paper-faithful baseline);
    # "ep": shard_map expert-parallel sorted dispatch (beyond-paper, SSPerf)
    moe_impl: str = "einsum"

    # SSM
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_head_dim: int = 64         # mamba2
    mamba_version: int = 1

    # hybrid (zamba2)
    shared_attn_every: int = 0     # apply shared attn block every N ssm blocks

    # enc-dec (seamless)
    n_enc_layers: int = 0
    frames_ratio: int = 4          # encoder frames = seq_len // ratio

    # vlm
    n_patches: int = 0             # vision patches per sample (pre-embedded)

    # numerics / memory
    grad_accum: int = 1            # microbatches per train step (see steps.py)
    param_dtype: str = "bfloat16"
    moment_dtype: str = "float32"
    remat: str = "full"            # none | full
    long_context_ok: bool = False  # may run long_500k
    attn_window_long: int = 8192   # hybrid window for long_500k decode

    # sharding hints (see launch/sharding.py)
    fsdp: bool = False             # extra weight sharding over "data"
    expert_axis: str = "model"     # mesh axis for the expert dimension

    # lowering: unroll layer scans (used by the roofline cost extrapolation —
    # XLA's HloCostAnalysis counts while bodies once, so per-unit costs are
    # measured on small UNROLLED variants and extrapolated to full depth)
    scan_unroll: bool = False

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.d_model // max(self.n_heads, 1))

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_held(self) -> int:
        e0, e1 = self.experts_held
        return e1 - e0

    @property
    def mla_scale(self) -> float:
        """Softmax scale of latent attention: (nope + rope)^-1/2, times the
        YaRN gain."""
        s = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        return s * (self.rope_yarn.softmax_gain if self.rope_yarn else 1.0)

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a multiple of 256 so embedding/head shard over any
        mesh axis (MaxText-style); loss labels never reference pad ids."""
        return -(-self.vocab // 256) * 256

    def _attn_params(self) -> int:
        d, h = self.d_model, self.n_heads
        if self.mla:
            r, nope = self.kv_lora_rank, self.qk_nope_head_dim
            rope, vd = self.qk_rope_head_dim, self.v_head_dim
            return d * h * (nope + rope) + d * (r + rope) \
                + r * h * (nope + vd) + h * vd * d
        return d * self.head_dim * (h * 2 + self.n_kv_heads * 2)

    def n_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + head)."""
        d, f, v = self.d_model, self.d_ff, self.vocab
        attn = self._attn_params()
        dense_ffn = 3 * d * f
        if self.family == "ssm":
            di = self.ssm_expand * d
            blk = d * 2 * di + di * (max(1, d // 16) + 2 * self.ssm_state) \
                + max(1, d // 16) * di + di * d + 4 * di
            core = self.n_layers * blk
        elif self.family == "hybrid":
            di = self.ssm_expand * d
            ng = 1
            blk = d * (2 * di + 2 * ng * self.ssm_state + di // self.ssm_head_dim) \
                + di * d
            core = self.n_layers * blk + attn + dense_ffn  # one shared block
        elif self.family == "moe":
            ef = self.expert_d_ff or f
            moe_layers = (self.n_layers - self.first_k_dense) // self.moe_every
            dense_layers = self.n_layers - moe_layers
            moe_blk = self.n_experts * 3 * d * ef + d * self.n_experts
            if self.shared_expert:
                moe_blk += 3 * d * (self.shared_expert_d_ff or f)
            core = moe_layers * (attn + moe_blk) + dense_layers * (attn + dense_ffn)
        elif self.family == "encdec":
            core = (self.n_enc_layers + self.n_layers) * (attn + dense_ffn) \
                + self.n_layers * attn  # cross attention
        else:
            core = self.n_layers * (attn + dense_ffn)
        emb = v * d * (1 if self.tie_embeddings else 2)
        return core + emb

    def n_active_params(self) -> int:
        """Active params per token (MoE: top_k experts only)."""
        if self.family != "moe":
            return self.n_params()
        d, f = self.d_model, self.d_ff
        ef = self.expert_d_ff or f
        attn = self._attn_params()
        moe_layers = (self.n_layers - self.first_k_dense) // self.moe_every
        dense_layers = self.n_layers - moe_layers
        act_blk = self.top_k * 3 * d * ef + d * self.n_experts
        if self.shared_expert:
            act_blk += 3 * d * (self.shared_expert_d_ff or f)
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return moe_layers * (attn + act_blk) \
            + dense_layers * (attn + 3 * d * f) + emb


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                      # train | prefill | decode


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def smoke_config(cfg: ArchConfig) -> ArchConfig:
    """Reduced same-family config for CPU smoke tests."""
    return replace(
        cfg,
        n_layers=min(cfg.n_layers, 4 if cfg.moe_every == 1 else 4),
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads > 1 else 1,
        d_ff=256,
        vocab=512,
        head_dim=32,
        n_experts=min(cfg.n_experts, 8) if cfg.n_experts else 0,
        top_k=min(cfg.top_k, 2) if cfg.top_k else 0,
        expert_d_ff=64 if cfg.expert_d_ff else None,
        ssm_state=min(cfg.ssm_state, 16) if cfg.ssm_state else 0,
        ssm_head_dim=32,
        n_enc_layers=min(cfg.n_enc_layers, 2),
        sliding_window=16 if cfg.sliding_window else None,
        n_patches=8 if cfg.n_patches else 0,
        mrope_sections=(4, 6, 6) if cfg.mrope_sections else None,
        param_dtype="float32",
        remat="none",
        shared_attn_every=cfg.shared_attn_every and 2,
        kv_lora_rank=64 if cfg.kv_lora_rank else 0,
        qk_nope_head_dim=32 if cfg.kv_lora_rank else 0,
        qk_rope_head_dim=16 if cfg.kv_lora_rank else 0,
        v_head_dim=32 if cfg.kv_lora_rank else 0,
        first_k_dense=min(cfg.first_k_dense, 1),
        shared_expert_d_ff=128 if cfg.shared_expert_d_ff else None,
        experts_held=(0, min(cfg.n_experts, 8)) if cfg.experts_held else None,
    )
