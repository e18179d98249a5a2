"""Work counts of a DeepSeek-V3-family model (latent attention, routed and
shared experts, leading dense layers), computed from shapes and from the
routing counters the program records: what the algorithm needs, whatever
implements it.

``s`` is :func:`benchlib.mla_moe_lm.sizes`. Routed work counts the (token,
choice) pairs that landed on the experts this chip holds, summed over the
MoE layers, as the program's counters give them.
"""

from __future__ import annotations


def _layer_weights(s: dict) -> dict:
    """Weights (elements) of each part of one layer's matmuls."""
    d, H, c, r = s["d_model"], s["n_heads"], s["kv_lora_rank"], s["qk_rope"]
    nope, dv, f = s["qk_nope"], s["v_head_dim"], s["d_ff"]
    return {
        "attn": d * H * (nope + r) + d * (c + r) + c * H * (nope + dv) + H * dv * d,
        "dense_mlp": 3 * d * s["d_ff_dense"],
        "shared": 3 * d * s["n_shared"] * f,
        "router": d * s["n_experts"],
        "expert": 3 * d * f,
    }


def _n_moe(s: dict) -> int:
    return s["n_layers"] - s["n_dense_layers"]


def moe_prefill_flops(s: dict, batch: int, seq: int, routed_pairs: float) -> float:
    """Model FLOPs of a causal prefill of ``batch`` sequences of ``seq``
    tokens: every layer's attention projections with keys and values
    decompressed from the latent, causal attention (query i attends to
    i + 1 keys), the dense layers' MLP, the shared experts and router of
    each MoE layer for every token, ``routed_pairs`` held-expert FFNs, and
    the LM head at the last position only."""
    w = _layer_weights(s)
    L, n_dense, n_moe = s["n_layers"], s["n_dense_layers"], _n_moe(s)
    H, qk, dv = s["n_heads"], s["qk_nope"] + s["qk_rope"], s["v_head_dim"]
    per_token = 2 * (L * w["attn"] + n_dense * w["dense_mlp"]
                     + n_moe * (w["shared"] + w["router"]))
    attn = 2 * H * (qk + dv) * seq * (seq + 1) / 2  # scores and values, causal
    return float(batch * (seq * per_token + L * attn + 2 * s["d_model"] * s["vocab"])
                 + 2 * w["expert"] * routed_pairs)
