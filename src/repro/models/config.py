"""Unified model configuration covering all 10 assigned architectures.

One frozen dataclass parameterizes every family (dense / moe / ssm / hybrid /
encdec / vlm); family-specific behavior keys off these fields inside the
model implementations. Exact per-arch instantiations live in
``repro/configs/<id>.py`` and are registered in
:mod:`repro.models.registry`.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int | None = None
    # MLP
    mlp_act: str = "silu"  # silu | gelu
    glu: bool = True
    # attention details
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    sliding_window: int | None = None  # all layers SWA (mixtral)
    local_global_period: int | None = None  # gemma2: every other layer local
    local_window: int = 4096
    attn_softcap: float | None = None
    logit_softcap: float | None = None
    # multi-head latent attention (DeepSeek-V2 §2.1); on when kv_lora_rank > 0.
    # No query compression: q comes from one d_model x heads projection.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # MoE
    n_experts: int = 0  # routed experts the router scores (its published width)
    top_k: int = 2
    capacity_factor: float = 1.25
    router_score: str = "softmax"  # softmax | sigmoid (+ correction bias for the choice)
    routed_scale: float = 1.0  # factor on the chosen weights, renormalised to sum to 1
    n_shared_experts: int = 0  # always-on experts, one MLP of n x d_ff
    first_dense_layers: int = 0  # leading layers with a dense MLP of d_ff_dense
    d_ff_dense: int = 0
    # the experts this chip holds: [expert_first, expert_first + experts_held)
    # of the n_experts routed ones (0 = all of them; expert parallelism)
    expert_first: int = 0
    experts_held: int = 0
    # SSM / recurrent
    ssm_state: int = 64
    ssm_conv: int = 4
    ssm_expand: int = 2
    slstm_every: int = 0  # xlstm: every j-th layer is an sLSTM block
    attn_every: int = 0  # zamba2: shared attention block every j layers
    # encoder-decoder (whisper)
    encoder_layers: int = 0
    encoder_seq: int = 1500  # whisper-base 30 s → 1500 frames (stub frontend)
    # vlm (pixtral)
    vision_patches: int = 1024  # stub ViT output length
    # numerics
    dtype: str = "bfloat16"
    norm_eps: float = 1e-6
    # training-time knobs (hillclimbing levers; see EXPERIMENTS.md §Perf)
    remat_policy: str = "nothing"  # nothing | dots | full
    seq_shard_activations: bool = True  # Megatron-SP style residual sharding
    attn_q_chunk: int = 1024
    attn_kv_chunk: int = 1024
    ssm_chunk: int = 256
    # dry-run FLOPs pass: unroll scans so HLO cost_analysis counts every
    # loop iteration (XLA counts while-loop bodies once).
    scan_unroll: bool = False
    # §Perf lever: gather FSDP-sharded weights at the use site instead of
    # letting GSPMD all-reduce contraction outputs (MaxText-style).
    weight_gather: bool = False
    # §Perf lever: shard decode KV-cache sequence over "model" (256-way
    # caches) and update caches in-place through the layer-scan carry.
    decode_cache_seq_shard: bool = False
    # §Perf lever: "default" or "pure_dp" (replicate params, batch-only
    # sharding — right call for sub-1B models on 256 chips).
    sharding_profile: str = "default"
    # §Perf lever: gradient-accumulation microbatches per step (memory).
    grad_accum: int = 1

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def n_held(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.n_experts

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_dense_layers if self.n_experts else 0

    @property
    def q_per_kv(self) -> int:
        return self.n_heads // self.n_kv_heads

    @property
    def is_recurrent(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def supports_long_context(self) -> bool:
        """Sub-quadratic decode at 512k: SSM/hybrid state or bounded SWA."""
        if self.is_recurrent:
            return True
        return self.sliding_window is not None and self.local_global_period is None

    @property
    def has_decoder(self) -> bool:
        return True  # all assigned archs have an autoregressive decoder

    def _attn_params(self) -> int:
        d, hd = self.d_model, self.hd
        if self.is_mla:
            H, c, r = self.n_heads, self.kv_lora_rank, self.qk_rope_head_dim
            qk = self.qk_nope_head_dim + r
            return (d * H * qk + d * (c + r) + c
                    + c * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * d)
        return d * (self.n_heads * hd) * 2 + d * (self.n_kv_heads * hd) * 2

    def param_count_dense(self) -> int:
        """Analytic parameter estimate (used for MODEL_FLOPS = 6·N·D).
        MoE: every routed expert of the model, whichever a chip holds."""
        d = self.d_model
        unit = 3 if self.glu else 2
        emb = self.vocab * d * 2  # embed + untied head
        attn = self._attn_params()
        if self.n_experts:
            ff_unit = self.n_experts * unit * d * self.d_ff
            router = d * self.n_experts
            mlp = ff_unit + router + unit * d * self.n_shared_experts * self.d_ff
        elif self.d_ff:
            mlp = unit * d * self.d_ff
        else:
            mlp = 0
        if self.family == "ssm":
            # mLSTM-ish block: in/out proj at expansion + gates
            di = self.ssm_expand * d
            mlp = 0
            attn = 2 * d * di * 2 + 3 * di  # up/gate + down, cheap gates
        per_layer = attn + mlp
        if self.family == "hybrid":
            # Mamba2 backbone layers + ONE shared attn+mlp block (weights
            # applied at multiple depths but stored once).
            di = self.ssm_expand * d
            dconv = di + 2 * self.n_heads * self.ssm_state
            mamba = (
                d * (2 * di + 2 * self.n_heads * self.ssm_state + self.n_heads)
                + self.ssm_conv * dconv
                + di * d
            )
            return int(emb + self.n_layers * mamba + per_layer)
        n_dense = self.first_dense_layers if self.n_experts else 0
        total = (emb + (self.n_layers - n_dense) * per_layer
                 + n_dense * (attn + unit * d * self.d_ff_dense))
        if self.encoder_layers:
            total += self.encoder_layers * per_layer
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: top_k of n_experts)."""
        if not self.n_experts:
            return self.param_count_dense()
        full = self.param_count_dense()
        ff_unit = (3 if self.glu else 2) * self.d_model * self.d_ff
        moe_total = self.n_moe_layers * self.n_experts * ff_unit
        moe_active = self.n_moe_layers * self.top_k * ff_unit
        return int(full - moe_total + moe_active)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    """One assigned input-shape cell."""

    name: str
    kind: str  # train | prefill | decode
    seq: int
    batch: int


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}


def cell_is_runnable(cfg: ModelConfig, shape: ShapeSpec) -> tuple[bool, str]:
    """(runnable, reason). long_500k needs sub-quadratic attention."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return False, "full attention at 512k ctx is quadratic — skipped per task spec"
    return True, ""
