"""Decoder-only LM covering the dense / moe / vlm families.

Layer stack runs as ``lax.scan`` over stacked per-layer params with
``jax.checkpoint`` (remat) on the block body — compact HLO for the 40-cell
dry-run and bounded activation memory. Per-layer attention patterns
(gemma2 local/global alternation) ride along the scan as flag arrays. An
MoE model's leading dense layers (``first_dense_layers``) are a stack of
their own (``params["dense_layers"]``), scanned ahead of the MoE stack
(``params["layers"]``).

Attention is GQA (:mod:`repro.models.layers`) or multi-head latent
attention (:mod:`repro.models.mla`, ``kv_lora_rank > 0``), whose cache holds
the latent ``ckv`` and the shared rotary key ``kpe`` per position in place
of per-head keys and values. An MoE model's caches also carry
``expert_tokens`` (MoE layers, held experts + 1) int32: the (token, choice)
pairs each held expert took in the step that wrote the cache, the last
column those routed to other chips' experts.

Loss never materializes full (B, S, V) logits: the LM head + cross-entropy
run in sequence chunks (critical for vocab=256k at 1M tokens).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as ly
from repro.models import mla
from repro.models import moe as moe_mod
from repro.models.config import ModelConfig
from repro.models.sharding import constrain

AUX_LOSS_WEIGHT = 0.01
LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _layer_windows(cfg: ModelConfig) -> list[int]:
    """Per-layer attention window (0 = full causal)."""
    out = []
    for i in range(cfg.n_layers):
        if cfg.sliding_window is not None:
            out.append(cfg.sliding_window)
        elif cfg.local_global_period and i % cfg.local_global_period == 0:
            out.append(cfg.local_window)
        else:
            out.append(0)
    return out


def _stacks(cfg: ModelConfig) -> list[tuple[str, int, int]]:
    """(params key, first layer, layer count) of each scanned stack in order:
    an MoE model's leading dense layers, then the rest."""
    n_dense = cfg.first_dense_layers if cfg.n_experts else 0
    out = [("dense_layers", 0, n_dense)] if n_dense else []
    return out + [("layers", n_dense, cfg.n_layers - n_dense)]


def init_block(rng, cfg: ModelConfig, moe: bool | None = None):
    moe = bool(cfg.n_experts) if moe is None else moe
    ks = jax.random.split(rng, 4)
    p = {
        "ln1": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
        "attn": mla.init_mla(ks[0], cfg) if cfg.is_mla else ly.init_attention(ks[0], cfg),
        "ln2": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
    }
    if moe:
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
    else:
        p["mlp"] = ly.init_mlp(ks[2], cfg, d_ff=cfg.d_ff_dense or None)
    return p


def block_logical_axes(cfg: ModelConfig, moe: bool | None = None):
    moe = bool(cfg.n_experts) if moe is None else moe
    p = {
        "ln1": {"scale": (None,)},
        "attn": mla.mla_logical_axes(cfg) if cfg.is_mla else ly.attention_logical_axes(cfg),
        "ln2": {"scale": (None,)},
    }
    if moe:
        p["moe"] = moe_mod.moe_logical_axes(cfg)
    else:
        p["mlp"] = ly.mlp_logical_axes(cfg)
    return p


def init(rng, cfg: ModelConfig):
    k_emb, k_layers, k_vis = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embedding": ly.init_embedding(k_emb, cfg),
        "ln_f": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
    }
    for key, first, n in _stacks(cfg):
        moe = key == "layers" and bool(cfg.n_experts)
        params[key] = jax.vmap(lambda k, moe=moe: init_block(k, cfg, moe))(
            layer_keys[first:first + n])
    if cfg.family == "vlm":
        params["vision_proj"] = ly.init_dense(k_vis, cfg.d_model, cfg.d_model, ly.dt(cfg))
    return params


def logical_axes(cfg: ModelConfig):
    """Pytree of logical-axis tuples matching init(); stacked layers get a
    leading None (layer axis unsharded)."""
    p = {
        "embedding": ly.embedding_logical_axes(cfg),
        "ln_f": {"scale": (None,)},
    }
    for key, _, _ in _stacks(cfg):
        blk = block_logical_axes(cfg, key == "layers" and bool(cfg.n_experts))
        p[key] = jax.tree.map(lambda axes: (None, *axes), blk,
                              is_leaf=lambda x: isinstance(x, tuple))
    if cfg.family == "vlm":
        p["vision_proj"] = ("embed", None)
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _remat_policy(cfg: ModelConfig):
    if cfg.remat_policy == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if cfg.remat_policy == "full":
        return jax.checkpoint_policies.everything_saveable
    return jax.checkpoint_policies.nothing_saveable


def _block_apply(cfg: ModelConfig, p, x, window):
    """One transformer block. window: traced int32 scalar (0 = full)."""
    h = ly.rmsnorm(p["ln1"], x, cfg.norm_eps)
    # Window is a per-layer static-pattern flag; lax.cond keeps one compiled
    # body per branch (local vs global) inside the scan.
    if cfg.is_mla:
        attn_out = mla.attention(p["attn"], cfg, h)[0]
    elif cfg.sliding_window is not None:
        attn_out = ly.attention(p["attn"], cfg, h, causal=True, window=cfg.sliding_window)
    elif cfg.local_global_period:
        attn_out = jax.lax.cond(
            window > 0,
            lambda hh: ly.attention(p["attn"], cfg, hh, causal=True, window=cfg.local_window),
            lambda hh: ly.attention(p["attn"], cfg, hh, causal=True, window=None),
            h,
        )
    else:
        attn_out = ly.attention(p["attn"], cfg, h, causal=True, window=None)
    x = x + attn_out
    x = constrain(x, "batch", "seq_sp", None)
    h = ly.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "moe" in p:
        mlp_out, aux = moe_mod.moe_mlp(p["moe"], cfg, h)
    else:
        mlp_out, aux = ly.mlp(p["mlp"], cfg, h), jnp.float32(0.0)
    x = x + mlp_out
    x = constrain(x, "batch", "seq_sp", None)
    return x, aux


def backbone(params, cfg: ModelConfig, x):
    """(B, S, d) → (B, S, d) through the scanned layer stacks."""
    block = functools.partial(_block_apply, cfg)
    block = jax.checkpoint(block, policy=_remat_policy(cfg))

    def body(carry, inp):
        x, aux_sum = carry
        p, w = inp
        x, aux = block(p, x, w)
        return (x, aux_sum + aux), None

    carry = (x, jnp.float32(0.0))
    for key, first, n in _stacks(cfg):
        windows = jnp.asarray(_layer_windows(cfg)[first:first + n], jnp.int32)
        carry, _ = jax.lax.scan(body, carry, (params[key], windows), unroll=cfg.scan_unroll)
    x, aux_sum = carry
    return ly.rmsnorm(params["ln_f"], x, cfg.norm_eps), aux_sum


def _inputs_to_embeddings(params, cfg: ModelConfig, batch):
    """tokens (+ patch embeddings for vlm) → (B, S_total, d)."""
    x = ly.embed(params["embedding"], cfg, batch["tokens"])
    if cfg.family == "vlm":
        vis = batch["patches"].astype(ly.dt(cfg)) @ params["vision_proj"]
        x = jnp.concatenate([vis, x], axis=1)
    return constrain(x, "batch", "seq_sp", None)


def chunked_ce_loss(params, cfg: ModelConfig, x, labels):
    """Scan the LM head + CE over sequence chunks; returns mean CE."""
    B, S, d = x.shape
    c = min(LOSS_CHUNK, S)
    assert S % c == 0
    nc = S // c
    xs = x.reshape(B, nc, c, d).swapaxes(0, 1)
    lbl = labels.reshape(B, nc, c).swapaxes(0, 1)

    def body(tot, inp):
        xc, lc = inp
        lg = ly.logits(params["embedding"], cfg, xc)  # (B, c, V) fp32
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, lc[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    tot, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, lbl), unroll=cfg.scan_unroll)
    return tot / (B * S)


def train_loss(params, cfg: ModelConfig, batch) -> jax.Array:
    """CE against pre-aligned next-token labels (+ MoE aux). Loss covers
    token positions only (vlm: the patch prefix is excluded)."""
    x = _inputs_to_embeddings(params, cfg, batch)
    x, aux = backbone(params, cfg, x)
    S_text = batch["tokens"].shape[1]
    x_text = x[:, -S_text:]
    loss = chunked_ce_loss(params, cfg, x_text, batch["labels"])
    return loss + AUX_LOSS_WEIGHT * aux


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------


def cache_len(cfg: ModelConfig, max_seq: int) -> int:
    if cfg.sliding_window is not None:
        return min(max_seq, cfg.sliding_window)
    return max_seq


def init_cache(cfg: ModelConfig, B: int, max_seq: int):
    Smax = cache_len(cfg, max_seq)
    L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    if cfg.is_mla:
        cache = {
            "ckv": jnp.zeros((L, B, Smax, cfg.kv_lora_rank), ly.dt(cfg)),
            "kpe": jnp.zeros((L, B, Smax, cfg.qk_rope_head_dim), ly.dt(cfg)),
        }
    else:
        cache = {
            "k": jnp.zeros((L, B, Smax, Hkv, hd), ly.dt(cfg)),
            "v": jnp.zeros((L, B, Smax, Hkv, hd), ly.dt(cfg)),
        }
    cache["slot_pos"] = jnp.full((L, Smax), -(2**30), jnp.int32)
    cache["pos"] = jnp.int32(0)
    if cfg.n_experts:
        cache["expert_tokens"] = jnp.zeros((cfg.n_moe_layers, cfg.n_held + 1), jnp.int32)
    return cache


def _mlp_apply(cfg: ModelConfig, p, h):
    """Inference MLP: (out, the MoE layer's expert_tokens or None)."""
    if "moe" in p:
        return moe_mod.moe_infer(p["moe"], cfg, h)
    return ly.mlp(p["mlp"], cfg, h), None


def _decode_mla(params, cfg: ModelConfig, x, cache):
    """Latent-attention decode: the caches ride in the layer scans' carry and
    each layer writes only its new position."""
    pos = cache["pos"]
    Smax = cache["ckv"].shape[2]
    slot = jnp.mod(pos, Smax)
    sp = cache["slot_pos"]

    def body(carry, inp):
        x, ckv, kpe = carry
        p, i = inp
        h = ly.rmsnorm(p["ln1"], x, cfg.norm_eps)
        out, c_new, r_new = mla.decode_attention(
            p["attn"], cfg, h, ckv[i], kpe[i], sp[i], pos)
        ckv = jax.lax.dynamic_update_slice(ckv, c_new[None].astype(ckv.dtype), (i, 0, slot, 0))
        kpe = jax.lax.dynamic_update_slice(kpe, r_new[None].astype(kpe.dtype), (i, 0, slot, 0))
        x = x + out
        h = ly.rmsnorm(p["ln2"], x, cfg.norm_eps)
        mlp_out, tokens = _mlp_apply(cfg, p, h)
        return (x + mlp_out, ckv, kpe), tokens

    carry = (x, cache["ckv"], cache["kpe"])
    routed = []
    for key, first, n in _stacks(cfg):
        carry, tokens = jax.lax.scan(body, carry, (params[key], jnp.arange(first, first + n)),
                                     unroll=cfg.scan_unroll)
        if tokens is not None:
            routed.append(tokens)
    x, ckv, kpe = carry
    sp = jax.lax.dynamic_update_slice(sp, jnp.full((sp.shape[0], 1), pos, sp.dtype), (0, slot))
    new_cache = {"ckv": ckv, "kpe": kpe, "slot_pos": sp, "pos": pos + 1}
    if routed:
        new_cache["expert_tokens"] = jnp.concatenate(routed)
    return x, new_cache


def decode_step(params, cfg: ModelConfig, token, cache):
    """token: (B, 1) int32 → (logits (B, 1, V) fp32, new cache)."""
    x = ly.embed(params["embedding"], cfg, token)
    if cfg.is_mla:
        x, new_cache = _decode_mla(params, cfg, x, cache)
        x = ly.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        return ly.logits(params["embedding"], cfg, x), new_cache
    if "dense_layers" in params:
        raise NotImplementedError("leading dense layers are served with latent attention")
    windows = jnp.asarray(_layer_windows(cfg), jnp.int32)
    pos = cache["pos"]

    def body(x, inp):
        p, ck, cv, sp, w = inp
        h = ly.rmsnorm(p["ln1"], x, cfg.norm_eps)
        window = cfg.sliding_window
        if cfg.local_global_period:
            # decode: window flag folded into slot_pos masking via w.
            window = None
        out, ck, cv, sp = ly.decode_attention(
            p["attn"], cfg, h, ck, cv, sp, pos, window=window
        )
        if cfg.local_global_period:
            # local layers additionally mask to the window.
            out_local, ck2, cv2, sp2 = ly.decode_attention(
                p["attn"], cfg, h, ck, cv, sp, pos, window=cfg.local_window
            )
            is_local = w > 0
            out = jnp.where(is_local, out_local, out)
        x = x + out
        h = ly.rmsnorm(p["ln2"], x, cfg.norm_eps)
        mlp_out, tokens = _mlp_apply(cfg, p, h)
        if tokens is None:
            return x + mlp_out, (ck, cv, sp)
        return x + mlp_out, (ck, cv, sp, tokens)

    x, ys = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"], cache["slot_pos"], windows),
        unroll=cfg.scan_unroll,
    )
    x = ly.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    lg = ly.logits(params["embedding"], cfg, x)
    new_cache = {"k": ys[0], "v": ys[1], "slot_pos": ys[2], "pos": pos + 1}
    if cfg.n_experts:
        new_cache["expert_tokens"] = ys[3]
    return lg, new_cache


def _prefill_layer(cfg: ModelConfig, S: int, Smax: int, carry, inp):
    """One layer of the prompt: (x,) → (x,), (this layer's cache slots,
    its slot positions[, its expert_tokens])."""
    x, = carry
    p, w = inp
    B = x.shape[0]
    h = ly.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.is_mla:
        out, k, v = mla.attention(p["attn"], cfg, h)  # the latent and the rotary key
    else:
        positions = jnp.arange(S)[None, :].astype(jnp.int32)
        q, k, v = ly._project_qkv(p["attn"], cfg, h, positions)
        if cfg.sliding_window is not None:
            window = cfg.sliding_window
        elif cfg.local_global_period:
            window = None  # global path; local layers masked below via cond
        else:
            window = None
        attn = ly.chunked_attention(
            cfg, q, k, v, causal=True, window=window, softcap=cfg.attn_softcap
        )
        if cfg.local_global_period:
            attn_local = ly.chunked_attention(
                cfg, q, k, v, causal=True, window=cfg.local_window, softcap=cfg.attn_softcap
            )
            attn = jnp.where(w > 0, attn_local, attn)
        out = attn.reshape(B, S, cfg.n_heads * cfg.hd) @ p["attn"]["wo"]
    x = x + out
    x = constrain(x, "batch", "seq_sp", None)
    h = ly.rmsnorm(p["ln2"], x, cfg.norm_eps)
    # Inference: dropless routing, so decode (S=1, can never drop)
    # reproduces prefill continuations token-exactly.
    mlp_out, tokens = _mlp_apply(cfg, p, h)
    x = x + mlp_out
    x = constrain(x, "batch", "seq_sp", None)
    ck, cv, sp = ly.fill_cache_from_prefill(k, v, Smax)
    if tokens is None:
        return (x,), (ck, cv, sp)
    return (x,), (ck, cv, sp, tokens)


def prefill(params, cfg: ModelConfig, batch, max_seq: int | None = None):
    """Run the full prompt, return (last-token logits, primed cache)."""
    x = _inputs_to_embeddings(params, cfg, batch)
    B, S, _ = x.shape
    max_seq = max_seq or S
    Smax = cache_len(cfg, max_seq)
    body = functools.partial(_prefill_layer, cfg, S, Smax)
    body = jax.checkpoint(body, policy=_remat_policy(cfg))
    outs = []
    for key, first, n in _stacks(cfg):
        windows = jnp.asarray(_layer_windows(cfg)[first:first + n], jnp.int32)
        (x,), ys = jax.lax.scan(
            body, (x,), (params[key], windows), unroll=cfg.scan_unroll
        )
        outs.append(ys)
    # per cache leaf, the stacks' layers in order
    ck, cv, sp = (outs[0][i] if len(outs) == 1 else jnp.concatenate([o[i] for o in outs])
                  for i in range(3))
    x = ly.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    last = ly.logits(params["embedding"], cfg, x[:, -1:])
    names = ("ckv", "kpe") if cfg.is_mla else ("k", "v")
    cache = {names[0]: ck, names[1]: cv, "slot_pos": sp, "pos": jnp.int32(S)}
    if cfg.n_experts:
        cache["expert_tokens"] = outs[-1][3]
    return last, cache


def prefill_tokens(params, cfg: ModelConfig, tokens, max_seq: int | None = None):
    """Tokens-only prefill contract for the fused serving tower.

    ``tokens`` is a plain (B, S) int32 array — traceable, so the serving
    step can jit it together with the storage decode (no host batch-dict
    construction between decode and prefill). Non-token modalities get zero
    extras: the vlm family sees an all-zero patch grid (the serving tower
    has no image side yet).
    """
    batch = {"tokens": tokens}
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros(
            (tokens.shape[0], cfg.vision_patches, cfg.d_model), jnp.float32
        )
    return prefill(params, cfg, batch, max_seq)


def cache_logical_axes(cfg: ModelConfig, B: int):
    """Logical axes matching init_cache's structure. B==1 (long-context)
    shards the cache sequence over 'model'; otherwise batch+kv-heads."""
    if cfg.is_mla:  # the latent is shared by every head: batch only
        lat = (None, "batch", None, None)
        axes = {"ckv": lat, "kpe": lat, "slot_pos": (None, None), "pos": ()}
    else:
        if B == 1:  # long-context: shard the cache sequence, not heads
            kv = (None, None, "kv_seq", None, None)
        elif cfg.decode_cache_seq_shard:
            # §Perf: batch × sequence sharding = full 256-way cache split
            # (kv_heads rarely divide the model axis; the sequence always does).
            kv = (None, "batch", "kv_seq", None, None)
        else:
            kv = (None, "batch", None, "kv_heads", None)
        axes = {"k": kv, "v": kv, "slot_pos": (None, None), "pos": ()}
    if cfg.n_experts:
        axes["expert_tokens"] = (None, None)
    return axes
