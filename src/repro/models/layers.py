"""Shared neural building blocks (pure JAX, functional params-as-pytrees).

Conventions:
  * params are nested dicts of jnp arrays; init fns take an `rng` and shapes.
  * activations flow as (batch, seq, d_model) in cfg.dtype; layernorm/softmax
    accumulate in fp32.
  * attention is GQA with chunked online-softmax (flash-style, pure JAX) for
    train/prefill, plain cached attention for decode.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models.config import ModelConfig
from repro.models.sharding import constrain


def dt(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_dense(rng, d_in: int, d_out: int, dtype, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.zeros((d,), dtype)}


def rmsnorm(params, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale); scale initialized to zeros.
    return (normed * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (math.log(theta) / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, half)
    cos = jnp.cos(ang)[..., :, None, :]  # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(rng, 4)
    p = {
        "wq": init_dense(ks[0], d, cfg.n_heads * hd, dt(cfg)),
        "wk": init_dense(ks[1], d, cfg.n_kv_heads * hd, dt(cfg)),
        "wv": init_dense(ks[2], d, cfg.n_kv_heads * hd, dt(cfg)),
        "wo": init_dense(ks[3], cfg.n_heads * hd, d, dt(cfg)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.n_heads * hd,), dt(cfg))
        p["bk"] = jnp.zeros((cfg.n_kv_heads * hd,), dt(cfg))
        p["bv"] = jnp.zeros((cfg.n_kv_heads * hd,), dt(cfg))
    return p


def attention_logical_axes(cfg: ModelConfig):
    p = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p.update({"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)})
    return p


def use_weight(cfg: ModelConfig, w, *axes):
    """§Perf weight_gather: constrain a stored (FSDP-sharded) weight to its
    compute layout (embed axis gathered) right before the contraction."""
    if not cfg.weight_gather:
        return w
    return constrain(w, *axes)


def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


def _project_qkv(params, cfg: ModelConfig, x, positions):
    B, S, _ = x.shape
    hd = cfg.hd
    q = x @ use_weight(cfg, params["wq"], None, "heads")
    k = x @ use_weight(cfg, params["wk"], None, "kv_heads")
    v = x @ use_weight(cfg, params["wv"], None, "kv_heads")
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", None, "heads", None)
    k = constrain(k, "batch", None, "kv_heads", None)
    v = constrain(v, "batch", None, "kv_heads", None)
    return q, k, v


def _block_attn(q, k, v, qpos, kpos, scale, softcap, causal, window):
    """One (q-chunk × kv-chunk) block. q: (B,qc,Hkv,G,hd), k/v: (B,kc,Hkv,hd).

    Returns (scores_exp (B,Hkv,G,qc,kc) numerator terms, row max, row sum)
    in the online-softmax decomposition.
    """
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32)
    s = _softcap(s * scale, softcap)
    dqk = qpos[:, None] - kpos[None, :]  # (qc, kc)
    mask = (kpos >= 0)[None, :]  # padded kv positions carry kpos < 0
    if causal:
        mask = mask & (dqk >= 0)
    if window is not None:
        mask = mask & (dqk < window)
    s = jnp.where(mask[None, None, None], s, -1e30)
    m = jnp.max(s, axis=-1)  # (B,Hkv,G,qc)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype), v)
    return o, m, l


def chunked_attention(
    cfg: ModelConfig,
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, Hkv, hd)
    v: jax.Array,
    *,
    causal: bool,
    window: int | None,
    softcap: float | None,
    q_offset: int = 0,
) -> jax.Array:
    """Flash-style online-softmax attention, scanned over q and kv chunks.

    Memory per step is O(q_chunk × kv_chunk). With ``window`` set, only the
    banded kv range [q_hi − window − qc, q_hi) is sliced per q-chunk, making
    SWA linear in sequence length.
    """
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]  # latent attention: values narrower than queries and keys
    Sq_real = Sq
    Skv = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    scale = 1.0 / math.sqrt(hd)
    qc = min(cfg.attn_q_chunk, Sq)
    kc = min(cfg.attn_kv_chunk, Skv)
    if Sq % qc != 0:  # pad queries; outputs trimmed at the end
        pad = qc * -(-Sq // qc) - Sq
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sq += pad
    kpos_all = jnp.arange(Skv)
    if Skv % kc != 0:  # pad keys; kpos < 0 masks them out in _block_attn
        pad = kc * -(-Skv // kc) - Skv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos_all = jnp.concatenate([kpos_all, jnp.full((pad,), -(2**30))])
        Skv += pad
    nq = Sq // qc
    q = q.reshape(B, nq, qc, Hkv, G, hd)
    nk = Skv // kc
    band = window is not None and window + qc < Skv
    if band:
        # Banded SWA: slice [hi − (window + qc) … hi) of kv per q-chunk.
        span_k = -(-(window + qc) // kc) * kc
    else:
        span_k = Skv

    def per_q_chunk(carry, qi):
        qblk = jax.lax.dynamic_index_in_dim(q, qi, axis=1, keepdims=False)
        qpos = q_offset + qi * qc + jnp.arange(qc)

        if band:
            hi = q_offset + (qi + 1) * qc
            start = jnp.clip(hi - span_k, 0, Skv - span_k)
            kblk_all = jax.lax.dynamic_slice_in_dim(k, start, span_k, axis=1)
            vblk_all = jax.lax.dynamic_slice_in_dim(v, start, span_k, axis=1)
            kpos_band = start + jnp.arange(span_k)
        else:
            kblk_all, vblk_all, kpos_band = k, v, kpos_all

        nkb = span_k // kc

        def per_kv_chunk(acc, ki):
            o_acc, m_acc, l_acc = acc
            kblk = jax.lax.dynamic_slice_in_dim(kblk_all, ki * kc, kc, axis=1)
            vblk = jax.lax.dynamic_slice_in_dim(vblk_all, ki * kc, kc, axis=1)
            kpos = jax.lax.dynamic_slice_in_dim(kpos_band, ki * kc, kc, axis=0)
            o, m, l = _block_attn(qblk, kblk, vblk, qpos, kpos, scale, softcap, causal, window)
            m_new = jnp.maximum(m_acc, m)
            c_old = jnp.exp(m_acc - m_new)
            c_new = jnp.exp(m - m_new)
            l_acc = l_acc * c_old + l * c_new
            o_acc = (
                o_acc * c_old.transpose(0, 3, 1, 2)[..., None]
                + o * c_new.transpose(0, 3, 1, 2)[..., None]
            )
            return (o_acc, m_new, l_acc), None

        o0 = jnp.zeros((B, qc, Hkv, G, hd_v), jnp.float32)
        m0 = jnp.full((B, Hkv, G, qc), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qc), jnp.float32)
        (o, m, l), _ = jax.lax.scan(
            per_kv_chunk, (o0, m0, l0), jnp.arange(nkb), unroll=cfg.scan_unroll
        )
        out = o / jnp.maximum(l.transpose(0, 3, 1, 2)[..., None], 1e-30)
        return carry, out.astype(v.dtype)

    _, outs = jax.lax.scan(per_q_chunk, None, jnp.arange(nq), unroll=cfg.scan_unroll)
    # outs: (nq, B, qc, Hkv, G, hd_v) → (B, Sq, H, hd_v), trimmed of q padding
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, Hkv, G, hd_v).reshape(B, Sq, H, hd_v)
    return out[:, :Sq_real]


def attention(
    params,
    cfg: ModelConfig,
    x: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    positions: jax.Array | None = None,
    kv_override: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """Full attention sublayer for train/prefill. x: (B, S, d)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    q, k, v = _project_qkv(params, cfg, x, positions)
    if kv_override is not None:  # cross-attention (whisper decoder)
        k, v = kv_override
    out = chunked_attention(
        cfg, q, k, v, causal=causal, window=window, softcap=cfg.attn_softcap
    )
    out = out.reshape(B, S, cfg.n_heads * cfg.hd)
    return out @ use_weight(cfg, params["wo"], "heads", None)


def decode_cache_axes(cfg: ModelConfig, B: int) -> tuple[str | None, ...]:
    """Logical axes of one layer's (B, Smax, Hkv·hd) decode cache. B == 1
    (long context) shards the sequence, not heads."""
    if B == 1:
        return (None, "kv_seq", None)
    if cfg.decode_cache_seq_shard:
        # §Perf: batch × sequence sharding = full 256-way cache split
        # (kv_heads rarely divide the model axis; the sequence always does).
        return ("batch", "kv_seq", None)
    return ("batch", None, "kv_heads")


def decode_qkv(params, cfg: ModelConfig, x: jax.Array, pos: jax.Array):
    """The new position's projections: q (B, 1, H, hd) and the cache rows
    k, v (B, 1, Hkv·hd) to write at slot ``pos % Smax``."""
    B = x.shape[0]
    q, k, v = _project_qkv(params, cfg, x, jnp.full((B, 1), pos, jnp.int32))
    return q, k.reshape(B, 1, -1), v.reshape(B, 1, -1)


def decode_attend(
    params,
    cfg: ModelConfig,
    q: jax.Array,  # (B, 1, H, hd)
    cache_k: jax.Array,  # (B, Smax, Hkv·hd), the new position already written
    cache_v: jax.Array,
    slot_pos: jax.Array,  # (Smax,) int32 absolute position per slot (−big = empty)
    pos: jax.Array,  # scalar int32: position of the new token
    *,
    window: int | jax.Array | None = None,  # 0 = full
) -> jax.Array:
    """The new position's attention over a (possibly ring-buffer) cache.

    A cache row holds a position's kv heads side by side (Hkv·hd lanes: dense
    on the TPU's 128-lane tiles even where hd = 64 alone is not), and the
    heads are contracted as block-diagonal products over that row: each
    query fills its own head's hd lanes and zeros elsewhere, and each head
    keeps its own block of the values' product. The row is read in the
    layout it is stored in, so a step relayouts no cache; the extra products
    are exact zeros, and a decode step stays bound by the cache's bytes.
    Masking uses per-slot absolute positions, so a sliding-window cache of
    size ``window`` serves unbounded contexts. Returns (B, 1, d).
    """
    B = q.shape[0]
    hd, Hkv, G = cfg.hd, cfg.n_kv_heads, cfg.q_per_kv
    axes = decode_cache_axes(cfg, B)
    row = Layout(major_to_minor=(0, 1, 2))
    cache_k = with_layout_constraint(constrain(cache_k, *axes), row)
    cache_v = with_layout_constraint(constrain(cache_v, *axes), row)
    eye = jnp.eye(Hkv, dtype=q.dtype)
    q_row = jnp.einsum("bhgd,hj->bjdhg", q.reshape(B, Hkv, G, hd), eye)
    q_row = q_row.reshape(B, Hkv * hd, Hkv * G)
    s = jnp.einsum("bkc,bcn->bnk", cache_k, q_row, preferred_element_type=jnp.float32)
    s = _softcap(s / math.sqrt(hd), cfg.attn_softcap)
    mask = slot_pos <= pos
    mask &= slot_pos >= 0
    if window is not None:
        mask &= (window <= 0) | (slot_pos > pos - window)
    s = jnp.where(mask[None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_row = jnp.einsum("bnk,bkc->bnc", p.astype(cache_v.dtype), cache_v,
                       preferred_element_type=jnp.float32)
    o = jnp.einsum("bhgjd,hj->bhgd", o_row.reshape(B, Hkv, G, Hkv, hd),
                   eye.astype(jnp.float32)).astype(cache_v.dtype)
    out = o.reshape(B, 1, cfg.n_heads * hd)
    return out @ use_weight(cfg, params["wo"], "heads", None)


def decode_attention(
    params,
    cfg: ModelConfig,
    x: jax.Array,  # (B, 1, d)
    cache_k: jax.Array,  # (B, Smax, Hkv·hd) — ring buffer when Smax < ctx
    cache_v: jax.Array,
    slot_pos: jax.Array,  # (Smax,)
    pos: jax.Array,
    *,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One decode step of one layer: project, write the new position at
    slot ``pos % Smax``, attend (:func:`decode_attend`).

    Returns (out, new_k, new_v, new_slot_pos).
    """
    q, k_new, v_new = decode_qkv(params, cfg, x, pos)
    slot = jnp.mod(pos, cache_k.shape[1])
    cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, slot, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, slot, axis=1)
    slot_pos = jax.lax.dynamic_update_slice_in_dim(
        slot_pos, jnp.full((1,), pos, slot_pos.dtype), slot, axis=0
    )
    out = decode_attend(params, cfg, q, cache_k, cache_v, slot_pos, pos, window=window)
    return out, cache_k, cache_v, slot_pos


def fill_cache_from_prefill(
    k: jax.Array, v: jax.Array, Smax: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Arrange the last Smax of (B, S, ...) prefill K/V (or, for latent
    attention, the latent and the rotary key) into ring slots of (B, Smax,
    row): a position's heads side by side, as :func:`decode_attend` reads.

    Position p lives in slot p mod Smax. The kept positions are consecutive,
    so the ring is the zero-padded tail rotated by a static shift: a pad and
    a roll, no scatter (the TPU compiler aborts on this batched scatter).
    """
    B, S = k.shape[:2]
    k, v = k.reshape(B, S, -1), v.reshape(B, S, -1)
    take = min(S, Smax)
    shift = (S - take) % Smax
    ck = jnp.roll(jnp.pad(k[:, S - take :], _seq_pad(k, Smax - take)), shift, axis=1)
    cv = jnp.roll(jnp.pad(v[:, S - take :], _seq_pad(v, Smax - take)), shift, axis=1)
    positions = np.arange(S - take, S)
    sp = np.full((Smax,), -(2**30), np.int32)
    sp[positions % Smax] = positions
    return ck, cv, jnp.asarray(sp)


def _seq_pad(a: jax.Array, n: int):
    """Pad widths that add n positions after axis 1 of a (B, S, ...) array."""
    return ((0, 0), (0, n)) + ((0, 0),) * (a.ndim - 2)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(rng, cfg: ModelConfig, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    p = {
        "wi": init_dense(ks[0], cfg.d_model, d_ff, dt(cfg)),
        "wo": init_dense(ks[1], d_ff, cfg.d_model, dt(cfg)),
    }
    if cfg.glu:
        p["wg"] = init_dense(ks[2], cfg.d_model, d_ff, dt(cfg))
    return p


def mlp_logical_axes(cfg: ModelConfig):
    p = {"wi": ("embed", "ff"), "wo": ("ff", "embed")}
    if cfg.glu:
        p["wg"] = ("embed", "ff")
    return p


def _act(cfg: ModelConfig, x):
    if cfg.mlp_act == "gelu":
        return jax.nn.gelu(x)
    return jax.nn.silu(x)


def mlp(params, cfg: ModelConfig, x):
    h = x @ use_weight(cfg, params["wi"], None, "ff")
    if cfg.glu:
        h = _act(cfg, x @ use_weight(cfg, params["wg"], None, "ff")) * h
    else:
        h = _act(cfg, h)
    h = constrain(h, "batch", None, "ff")
    return h @ use_weight(cfg, params["wo"], "ff", None)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def init_embedding(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 2)
    return {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32)).astype(dt(cfg)),
        "head": init_dense(ks[1], cfg.d_model, cfg.vocab, dt(cfg)),
    }


def embedding_logical_axes(cfg: ModelConfig):
    return {"embed": ("vocab", "embed"), "head": ("embed", "vocab")}


def embed(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt(cfg))
    return x * math.sqrt(cfg.d_model)


def logits(params, cfg: ModelConfig, x):
    out = x @ use_weight(cfg, params["head"], None, "vocab")
    out = _softcap(out.astype(jnp.float32), cfg.logit_softcap)
    return constrain(out, "batch", None, "vocab")


def cross_entropy(logit, labels):
    """Mean next-token CE. logit: (B,S,V) fp32, labels: (B,S) int32."""
    lse = jax.scipy.special.logsumexp(logit, axis=-1)
    gold = jnp.take_along_axis(logit, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
