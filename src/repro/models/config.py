"""Architecture configuration for the model zoo.

One :class:`ArchConfig` describes any of the 10 assigned architectures via a
periodic layer pattern (scanned) plus an optional unrolled prelude — this is
what lets qwen-style dense stacks, DeepSeek MLA+MoE, Jamba's 1:7
Mamba/attention interleave and xLSTM's mLSTM/sLSTM mix share one model
implementation (models/transformer.py).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import List, Optional, Tuple


@dataclass(frozen=True)
class LayerSpec:
    block: str          # "attn" | "mamba" | "mlstm" | "slstm"
    ffn: str            # "swiglu" | "gelu" | "moe" | "none"


@dataclass(frozen=True)
class YaRN:
    """YaRN rotary scaling (arXiv:2309.00071) in DeepSeek-V2's published
    form (``rope_scaling`` of type ``yarn``): rotary frequencies blended
    between interpolated and extrapolated by a linear ramp over the
    rotary dimensions, and the attention's softmax scale multiplied by
    ``mscale(factor, mscale_all_dim) ** 2``."""
    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass
class ArchConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int

    # layer layout: prelude (unrolled) + pattern repeated to fill n_layers
    pattern: Tuple[LayerSpec, ...] = (LayerSpec("attn", "swiglu"),)
    prelude: Tuple[LayerSpec, ...] = ()

    head_dim: Optional[int] = None          # default d_model // n_heads
    qk_norm: bool = False
    causal: bool = True
    encoder_only: bool = False
    norm: str = "rmsnorm"                    # "rmsnorm" | "layernorm"
    rope_theta: float = 1.0e6
    # None: plain rotary frequencies.  Read by MLA only.
    rope_scaling: Optional[YaRN] = None
    tie_embeddings: bool = False

    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    d_ff_expert: int = 0
    # "dense": every expert runs every token, masked by the routing
    # weights (weight-local under EP sharding; O(E) flops/token; the
    # reference the grouped path is tested against).  "grouped": the
    # (token, expert) pairs sorted by expert and run as grouped matrix
    # products (jax.lax.ragged_dot), O(top_k) flops/token, no token
    # dropped at any batch size.
    moe_impl: str = "dense"
    # router: softmax over every expert, then the top_k of it.  True
    # renormalises the top_k gates to sum to 1 (the same as a softmax over
    # the top_k logits); DeepSeek-V2 publishes False.
    norm_topk_prob: bool = True

    # --- MLA (DeepSeek-V2) ---
    mla: bool = False
    kv_lora: int = 512
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    v_head_dim: int = 128

    # --- Mamba ---
    mamba_d_state: int = 16
    mamba_conv: int = 4
    mamba_expand: int = 2

    # --- xLSTM ---
    xlstm_proj_factor: float = 2.0

    # --- modality frontend (stubbed: precomputed embeddings) ---
    frontend: str = "none"                   # "none" | "patch" | "audio"
    frontend_seq: int = 0                    # frontend positions per sample

    # --- mHC hyper-connections (paper RQ3 feature; off by default) ---
    hyper_connections: int = 0               # number of residual streams
    sinkhorn_iters: int = 5

    dtype: str = "bfloat16"
    # attention: "auto" runs the generated flash chain on a TPU and XLA
    # elsewhere; "xla" runs the reference on every backend
    attn_impl: str = "auto"
    remat: str = "full"                      # "none" | "dots" | "full"
    # decode/serving: unroll the layer loop (python loop, static parameter
    # slices, per-layer cache arrays).  Scanning over a layer-stacked KV
    # cache makes GSPMD involuntarily rematerialize (all-gather) the cache
    # every step — see EXPERIMENTS.md §Perf iteration 1.
    serve_unroll_layers: bool = True
    # KV cache dtype of GQA/MHA attention: "model" (the model dtype) or
    # "int8" — per-position per-head max-abs quantization.  DEFAULT int8:
    # without it the 32k-decode cells exceed v5e HBM (qwen3: 137 GB temp
    # vs 16 GB) and the memory roofline term is 2.8x worse (§Perf
    # iteration 2).  An MLA cache holds the latent in the model dtype and
    # takes "model" only (layers.init_mla_cache refuses "int8").
    kv_cache_dtype: str = "int8"

    def __post_init__(self):
        period = len(self.pattern)
        body = self.n_layers - len(self.prelude)
        assert body >= 0 and (period == 0 or body % period == 0), (
            f"{self.name}: {self.n_layers} layers != prelude "
            f"{len(self.prelude)} + k * period {period}")

    @property
    def repeats(self) -> int:
        return (self.n_layers - len(self.prelude)) // len(self.pattern)

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def scaled(self, **overrides) -> "ArchConfig":
        """Reduced-config clone (smoke tests)."""
        return dataclasses.replace(self, **overrides)

    # ---- parameter counting (for roofline MODEL_FLOPS) -----------------
    def param_count(self) -> int:
        d, v = self.d_model, self.vocab
        hd = self.resolved_head_dim
        n = v * d  # embed
        if not self.tie_embeddings and not self.encoder_only:
            n += v * d
        if self.encoder_only:
            n += v * d  # classifier head

        def layer_params(spec: LayerSpec) -> int:
            p = 2 * d  # norms
            if spec.block == "attn":
                if self.mla:
                    q_dim = self.n_heads * (self.nope_head_dim
                                            + self.rope_head_dim)
                    p += d * q_dim
                    p += d * (self.kv_lora + self.rope_head_dim)
                    p += self.kv_lora * self.n_heads * (self.nope_head_dim
                                                        + self.v_head_dim)
                    p += self.n_heads * self.v_head_dim * d
                else:
                    p += d * self.n_heads * hd
                    p += 2 * d * self.n_kv_heads * hd
                    p += self.n_heads * hd * d
            elif spec.block == "mamba":
                di = self.mamba_expand * d
                p += d * 2 * di + di * self.mamba_conv
                p += di * (2 * self.mamba_d_state + di // 16 * 0 + 1)
                p += di * d + di  # out proj + dt bias
            elif spec.block in ("mlstm", "slstm"):
                di = int(self.xlstm_proj_factor * d)
                p += d * 2 * di + 4 * di * di // max(1, self.n_heads) \
                    + di * d
            if spec.ffn == "swiglu":
                p += 3 * d * self.d_ff
            elif spec.ffn == "gelu":
                p += 2 * d * self.d_ff
            elif spec.ffn == "moe":
                dff = self.d_ff_expert or self.d_ff
                p += d * self.n_experts  # router
                p += self.n_experts * 3 * d * dff
                p += self.n_shared_experts * 3 * d * dff
            return p

        for spec in self.prelude:
            n += layer_params(spec)
        for spec in self.pattern:
            n += layer_params(spec) * self.repeats
        return n

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if self.n_experts == 0:
            return self.param_count()
        full = self.param_count()
        dff = self.d_ff_expert or self.d_ff
        moe_layers = sum(1 for s in self.prelude if s.ffn == "moe") + \
            sum(1 for s in self.pattern if s.ffn == "moe") * self.repeats
        unused = (self.n_experts - self.top_k) * 3 * self.d_model * dff
        return full - moe_layers * unused
