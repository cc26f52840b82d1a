"""Model / shape configuration schema and registry.

A ``ModelConfig`` is the architecture part of an EASEY ``AppSpec``: a
portable, target-agnostic description (the paper's Dockerfile analogue).
Deployment decisions (microbatches, remat, sharding rules, kernel choice)
are *not* stored here — the AutoTuner derives them per target and records
them in a DeploymentPlan, exactly like the paper injects
``###includelocalmpi###`` bricks at build time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense|moe|ssm_xlstm|hybrid_mamba|encdec|vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                # 0 -> d_model // num_heads
    activation: str = "silu"         # silu|gelu|geglu|sq_relu
    norm: str = "rmsnorm"            # rmsnorm|layernorm
    pos: str = "rope"                # rope|learned|sinusoidal|none
    rope_fraction: float = 1.0
    rope_theta: float = 10000.0
    rope_yarn: Yarn | None = None
    qkv_bias: bool = False
    tie_embeddings: bool = False
    causal: bool = True
    max_position: int = 1 << 20
    activation_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    # --- MoE ---
    num_experts: int = 0
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    moe_d_ff: int = 0                # routed and shared expert width (0: d_ff)
    shared_experts: int = 0          # always-on experts of width moe_d_ff
    first_dense_layers: int = 0      # leading layers with a dense d_ff MLP
    norm_topk: bool = True           # renormalise the top-k gates
    # the share of the routed experts held here: experts_held (0: all) from
    # expert_offset; the router still scores all num_experts
    experts_held: int = 0
    expert_offset: int = 0
    # --- latent attention (MLA; kv_lora_rank 0 = ordinary attention) ---
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # --- encoder-decoder (whisper) ---
    num_encoder_layers: int = 0
    # --- VLM (llava) ---
    num_patches: int = 0
    # --- SSM / hybrid ---
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_expand: int = 2
    ssm_chunk: int = 256
    conv_width: int = 4
    slstm_every: int = 0             # xlstm: every k-th block is sLSTM
    shared_attn_period: int = 0      # zamba2: shared attn block cadence
    window: int = 0                  # sliding-window attention (0 = full)
    # --- misc ---
    sub_quadratic: bool = False      # eligible for long_500k
    notes: str = ""

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.num_heads)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # train | prefill | decode
    serve_replicas: int = 1          # serve: engines sharing the HBM budget
    serve_repetitiveness: float = 0.0  # serve: trace n-gram self-overlap in
    #                                    [0, 1] — the tuner's signal for
    #                                    picking plan.serve_spec_k


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

ARCHS: dict[str, dict] = {}


def register(cfg: ModelConfig, smoke: ModelConfig,
             skip_shapes: tuple[str, ...] = ()) -> ModelConfig:
    ARCHS[cfg.name] = {"full": cfg, "smoke": smoke, "skip_shapes": skip_shapes}
    # smoke configs are addressable archs too (runnable examples/drivers)
    ARCHS[smoke.name] = {"full": smoke, "smoke": smoke,
                         "skip_shapes": skip_shapes, "is_smoke": True}
    return cfg


def get_config(arch: str) -> ModelConfig:
    return ARCHS[arch]["full"]


def smoke_config(arch: str) -> ModelConfig:
    return ARCHS[arch]["smoke"]


def list_archs(include_smoke: bool = False) -> list[str]:
    return sorted(a for a, m in ARCHS.items()
                  if include_smoke or not m.get("is_smoke"))


def cells(include_skipped: bool = False):
    """All (arch, shape) dry-run cells; skipped cells flagged."""
    out = []
    for arch in list_archs():
        meta = ARCHS[arch]
        if meta["full"].family == "stencil":
            continue  # LULESH has its own shape axis (benchmarks)
        for shape in SHAPES.values():
            skipped = shape.name in meta["skip_shapes"]
            if skipped and not include_skipped:
                continue
            out.append((arch, shape.name, skipped))
    return out
