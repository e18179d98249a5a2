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
    return least_s(ops, byts, peak_flops, peak_bytes_per_s)[0]


def _dense_matmul_params(cfg: dict) -> tuple[int, int]:
    """(weights of one layer's matmuls, weights of the LM head) of a gated
    MLP decoder; the head is the tied V x d embedding, counted once."""
    d, H, Hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, ff, V = cfg["head_dim"], cfg["d_ff"], cfg["vocab"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff, V * d


def dense_prefill_flops(cfg: dict, batch: int, seq: int) -> float:
    """Model FLOPs of a causal prefill of ``batch`` sequences of ``seq``
    tokens: every weight matmul, the causal attention (query i attends to
    i + 1 keys), and the LM head at the last position only.

    ``cfg`` holds ``d_model, n_heads, n_kv_heads, head_dim, d_ff, n_layers,
    vocab`` (a gated MLP: three d x d_ff matrices)."""
    H, hd, L = cfg["n_heads"], cfg["head_dim"], cfg["n_layers"]
    layer_mm, head = _dense_matmul_params(cfg)
    attn = 2 * 2 * H * hd * seq * (seq + 1) / 2  # scores and values, causal
    return float(batch * (seq * L * 2 * layer_mm + L * attn + 2 * head))


def dense_decode_step_work(cfg: dict, attended) -> tuple[float, float]:
    """(operations, bytes) of one cached decode step of a batch of sequences,
    the i-th attending ``attended[i]`` positions (its new one included).

    Operations: 2 x the matmul weights (every layer's and the LM head's) per
    sequence, plus attention scores and values over the attended positions.
    Bytes, ``cfg["dtype_bytes"]`` to an element: every weight read once (the
    tied LM head once, the layers' biases and norms, the final norm), the
    keys and values of the attended positions read, and the new position's
    written. Cache slots that no sequence attends yet are not counted."""
    d, H, Hkv = cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"]
    hd, L = cfg["head_dim"], cfg["n_layers"]
    layer_mm, head = _dense_matmul_params(cfg)
    B, positions = len(attended), sum(attended)
    ops = 2.0 * (L * layer_mm + head) * B + L * 2 * 2 * H * hd * positions
    layer_other = (H + 2 * Hkv) * hd + 2 * d  # q, k, v biases; two norms
    weights = L * (layer_mm + layer_other) + head + d
    kv = L * 2 * Hkv * hd * (positions + B)  # attended read, new position written
    return ops, float((weights + kv) * cfg["dtype_bytes"])


def least_s(ops: float, byts: float, peak_flops: float,
            peak_bytes_per_s: float) -> tuple[float, str]:
    """Least time of work on a chip and what bounds it: the larger of the
    operations over the peak and the bytes over the bandwidth."""
    t_ops, t_bytes = ops / peak_flops, byts / peak_bytes_per_s
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")
