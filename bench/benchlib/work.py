"""Work counts computed from shapes: codec bytes and operations, model FLOPs.

These are what the algorithm needs, whatever implements it, so padding to a
compile bucket or computing masked-out attention shows as a lost share of
the roofline or of the peak.
"""

from __future__ import annotations


def codec_item_work(m: int, k: int, B: int) -> tuple[float, float]:
    """(operations, bytes) of one GF(256) product of an (m, k) coding matrix
    with k strips of B bytes, computed as the GF(2) bit-matrix product
    (8m x 8k) @ (8k x B): 2 * 8m * 8k * B operations, k + m strips moved."""
    return 2.0 * (8 * m) * (8 * k) * B, float((k + m) * B)


def codec_call_least_s(items, peak_flops: float, peak_bytes_per_s: float) -> float:
    """Least time of one kernel call over ``items`` [(m, k, B), ...]: the
    larger of its operations over the peak and its bytes over the bandwidth."""
    ops = byts = 0.0
    for m, k, B in items:
        o, b = codec_item_work(m, k, B)
        ops += o
        byts += b
    return max(ops / peak_flops, byts / peak_bytes_per_s)


def dense_prefill_flops(cfg: dict, batch: int, seq: int) -> float:
    """Model FLOPs of a causal prefill of ``batch`` sequences of ``seq``
    tokens: every weight matmul, the causal attention (query i attends to
    i + 1 keys), and the LM head at the last position only.

    ``cfg`` holds ``d_model, n_heads, n_kv_heads, head_dim, d_ff, n_layers,
    vocab`` (a gated MLP: three d x d_ff matrices)."""
    d, H, Hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff, L, V = cfg["head_dim"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    per_token = 2 * d * H * hd + 2 * 2 * d * Hkv * hd + 2 * H * hd * d + 3 * 2 * d * ff
    attn = 2 * 2 * H * hd * seq * (seq + 1) / 2  # scores and values, causal
    return float(batch * (seq * L * per_token + L * attn + 2 * d * V))
