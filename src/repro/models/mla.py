"""Multi-head latent attention (MLA, DeepSeek-V2 §2.1.2-2.1.3), without query
compression (``q_lora_rank`` null, as in Moonlight-16B-A3B).

Per token x (d_model):

* q = x W_q, per head a "nope" part (``qk_nope_head_dim``) and a rotary part
  (``qk_rope_head_dim``), RoPE on the rotary part;
* [c; k_r] = x W_kv_a: the latent c (``kv_lora_rank``), RMS-normed, and one
  rotary key k_r shared by every head, RoPE applied;
* [k_nope; v] = c W_kv_b per head; the key is [k_nope; k_r];
* softmax over q·k / sqrt(qk_nope + qk_rope), the values summed, then W_o.

Prefill decompresses keys and values from the latent and runs the chunked
attention. Decode attends in the latent space: the per-head up-projections
are absorbed into the query (q_nope W_kb) and the output (Σ p c) W_vb, so
the cache holds only c and k_r, ``kv_lora_rank + qk_rope_head_dim`` values
a position, for every head at once.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models import layers as ly
from repro.models.config import ModelConfig

#: ``jax.named_scope`` of the attention sublayer inside prefill and decode
SCOPE = "mla.attend"


def init_mla(rng, cfg: ModelConfig):
    d, H, c, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 4)
    return {
        "wq": ly.init_dense(ks[0], d, H * (nope + r), ly.dt(cfg)),
        "wkv_a": ly.init_dense(ks[1], d, c + r, ly.dt(cfg)),
        "kv_norm": ly.init_rmsnorm(c, ly.dt(cfg)),
        "wkv_b": ly.init_dense(ks[2], c, H * (nope + dv), ly.dt(cfg)),
        "wo": ly.init_dense(ks[3], H * dv, d, ly.dt(cfg)),
    }


def mla_logical_axes(cfg: ModelConfig):
    return {
        "wq": ("embed", "heads"),
        "wkv_a": ("embed", None),
        "kv_norm": {"scale": (None,)},
        "wkv_b": (None, "heads"),
        "wo": ("heads", "embed"),
    }


def _queries(params, cfg: ModelConfig, x, positions):
    """(q_nope (B, S, H, nope), q_rope (B, S, H, rope) with RoPE applied)."""
    B, S, _ = x.shape
    nope = cfg.qk_nope_head_dim
    q = (x @ params["wq"]).reshape(B, S, cfg.n_heads, nope + cfg.qk_rope_head_dim)
    return q[..., :nope], ly.rope(q[..., nope:], positions, cfg.rope_theta)


def latent(params, cfg: ModelConfig, x, positions):
    """What the cache keeps of each position: (c (B, S, kv_lora_rank) normed,
    k_r (B, S, qk_rope_head_dim) with RoPE applied)."""
    c = cfg.kv_lora_rank
    kv = x @ params["wkv_a"]
    ckv = ly.rmsnorm(params["kv_norm"], kv[..., :c], cfg.norm_eps)
    kpe = ly.rope(kv[..., None, c:], positions, cfg.rope_theta)[..., 0, :]
    return ckv, kpe


def _up_projections(params, cfg: ModelConfig):
    """W_kv_b split per head: (W_kb (c, H, nope), W_vb (c, H, v))."""
    nope = cfg.qk_nope_head_dim
    w = params["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, nope + cfg.v_head_dim)
    return w[..., :nope], w[..., nope:]


def attention(params, cfg: ModelConfig, x, positions=None):
    """Train / prefill: x (B, S, d) → (out (B, S, d), c, k_r), keys and
    values decompressed from the latent, causal."""
    B, S, _ = x.shape
    H = cfg.n_heads
    if positions is None:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
    with jax.named_scope(SCOPE):
        q_nope, q_rope = _queries(params, cfg, x, positions)
        ckv, kpe = latent(params, cfg, x, positions)
        w_kb, w_vb = _up_projections(params, cfg)
        k_nope = jnp.einsum("bsc,chn->bshn", ckv, w_kb)
        v = jnp.einsum("bsc,chv->bshv", ckv, w_vb)
        k_rope = jnp.broadcast_to(kpe[:, :, None, :], (B, S, H, cfg.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate([k_nope, k_rope.astype(k_nope.dtype)], axis=-1)
        out = ly.chunked_attention(cfg, q, k, v, causal=True, window=None, softcap=None)
        out = out.reshape(B, S, H * cfg.v_head_dim) @ params["wo"]
    return out, ckv, kpe


def decode_attention(params, cfg: ModelConfig, x, ckv_cache, kpe_cache, slot_pos, pos):
    """One decode step in the latent space.

    x: (B, 1, d); ckv_cache (B, Smax, c) and kpe_cache (B, Smax, rope) hold
    every earlier position of this layer; the new position's latent is
    returned for the caller to write at slot ``pos % Smax`` (it is attended
    here already). slot_pos: (Smax,) absolute position per slot, −big =
    empty. Returns (out (B, 1, d), c_new (B, 1, c), k_r_new (B, 1, rope)).
    """
    B = x.shape[0]
    H = cfg.n_heads
    positions = jnp.full((B, 1), pos, jnp.int32)
    with jax.named_scope(SCOPE):
        q_nope, q_rope = _queries(params, cfg, x, positions)  # (B, 1, H, ·)
        c_new, r_new = latent(params, cfg, x, positions)  # (B, 1, ·)
        w_kb, w_vb = _up_projections(params, cfg)
        q_lat = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_kb,
                           preferred_element_type=jnp.float32).astype(ckv_cache.dtype)
        s = jnp.einsum("bhc,bsc->bhs", q_lat, ckv_cache, preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0], kpe_cache,
                           preferred_element_type=jnp.float32)
        s_new = (jnp.einsum("bhc,bc->bh", q_lat, c_new[:, 0],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bhr,br->bh", q_rope[:, 0], r_new[:, 0],
                              preferred_element_type=jnp.float32))
        scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
        # the slot the new position will take is attended through s_new
        slot = jnp.mod(pos, ckv_cache.shape[1])
        live = (slot_pos >= 0) & (slot_pos < pos) & (jnp.arange(slot_pos.shape[0]) != slot)
        s = jnp.where(live[None, None, :], s * scale, -1e30)
        s_all = jnp.concatenate([s, (s_new * scale)[..., None]], axis=-1)
        p = jax.nn.softmax(s_all, axis=-1)
        o_lat = jnp.einsum("bhs,bsc->bhc", p[..., :-1].astype(ckv_cache.dtype), ckv_cache,
                           preferred_element_type=jnp.float32)
        o_lat = o_lat + p[..., -1:] * c_new[:, 0, None, :].astype(jnp.float32)
        o = jnp.einsum("bhc,chv->bhv", o_lat.astype(x.dtype), w_vb)
        out = o.reshape(B, 1, H * cfg.v_head_dim) @ params["wo"]
    return out, c_new, r_new
