"""Zamba2-style hybrid: a scanned Mamba2 backbone with one SHARED
attention+MLP block (single weight copy) applied every ``attn_every``
backbone layers.

The shared block's weights are closure constants of the layer scan; each
application site keeps its own KV cache (weights are shared, activations are
not). The shared attention uses a sliding window (`local_window`) so the
512k-context decode cell runs with O(window) memory — a documented
adaptation (real Zamba2 uses full attention; the window is what makes
long_500k admissible, see DESIGN.md §4)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as ly
from repro.models import ssm
from repro.models.config import ModelConfig
from repro.models.lm import _remat_policy, chunked_ce_loss
from repro.models.sharding import constrain


def _attn_flags(cfg: ModelConfig) -> tuple[jnp.ndarray, jnp.ndarray, int]:
    """(apply_attn flag per layer, attn slot index per layer, n_sites)."""
    flags, slots = [], []
    site = 0
    for i in range(cfg.n_layers):
        hit = cfg.attn_every > 0 and (i + 1) % cfg.attn_every == 0
        flags.append(hit)
        slots.append(site if hit else 0)
        if hit:
            site += 1
    return (
        jnp.asarray(flags, jnp.int32),
        jnp.asarray(slots, jnp.int32),
        site,
    )


def init(rng, cfg: ModelConfig):
    k_emb, k_layers, k_attn, k_mlp = jax.random.split(rng, 4)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)

    def init_backbone_layer(k):
        return {
            "ln": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
            "mamba": ssm.init_mamba2(k, cfg),
        }

    return {
        "embedding": ly.init_embedding(k_emb, cfg),
        "layers": jax.vmap(init_backbone_layer)(layer_keys),
        "shared": {
            "ln1": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
            "attn": ly.init_attention(k_attn, cfg),
            "ln2": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
            "mlp": ly.init_mlp(k_mlp, cfg),
        },
        "ln_f": ly.init_rmsnorm(cfg.d_model, ly.dt(cfg)),
    }


def logical_axes(cfg: ModelConfig):
    norm = {"scale": (None,)}
    backbone = {
        "ln": {"scale": (None, None)},
        "mamba": jax.tree.map(
            lambda axes: (None, *axes), ssm.mamba2_logical_axes(cfg),
            is_leaf=lambda x: isinstance(x, tuple),
        ),
    }
    return {
        "embedding": ly.embedding_logical_axes(cfg),
        "layers": backbone,
        "shared": {
            "ln1": norm,
            "attn": ly.attention_logical_axes(cfg),
            "ln2": norm,
            "mlp": ly.mlp_logical_axes(cfg),
        },
        "ln_f": norm,
    }


def _shared_block(shared, cfg: ModelConfig, x):
    h = ly.rmsnorm(shared["ln1"], x)
    x = x + ly.attention(shared["attn"], cfg, h, causal=True, window=cfg.local_window)
    h = ly.rmsnorm(shared["ln2"], x)
    x = x + ly.mlp(shared["mlp"], cfg, h)
    return x


def backbone(params, cfg: ModelConfig, x):
    flags, _, _ = _attn_flags(cfg)
    shared = params["shared"]

    def block(p, x, flag):
        h = ly.rmsnorm(p["ln"], x)
        out, _ = ssm.mamba2_block(p["mamba"], cfg, h)
        x = x + out
        x = constrain(x, "batch", None, None)
        x = jax.lax.cond(flag > 0, lambda z: _shared_block(shared, cfg, z), lambda z: z, x)
        return constrain(x, "batch", None, None)

    block = jax.checkpoint(block, policy=_remat_policy(cfg))

    def body(x, inp):
        p, f = inp
        return block(p, x, f), None

    x, _ = jax.lax.scan(body, x, (params["layers"], flags), unroll=cfg.scan_unroll)
    return ly.rmsnorm(params["ln_f"], x)


def train_loss(params, cfg: ModelConfig, batch) -> jax.Array:
    x = ly.embed(params["embedding"], cfg, batch["tokens"])
    x = backbone(params, cfg, x)
    return chunked_ce_loss(params, cfg, x, batch["labels"])


# -- serving ------------------------------------------------------------------


def _stacked_mamba_state(cfg: ModelConfig, B: int):
    st = ssm.mamba2_state_init(cfg, B)
    return jax.tree.map(lambda s: jnp.stack([s] * cfg.n_layers), st)


def init_cache(cfg: ModelConfig, B: int, max_seq: int):
    _, _, n_sites = _attn_flags(cfg)
    Smax = min(max_seq, cfg.local_window)
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    return {
        "mamba": _stacked_mamba_state(cfg, B),
        "k": jnp.zeros((n_sites, B, Smax, Hkv * hd), ly.dt(cfg)),
        "v": jnp.zeros((n_sites, B, Smax, Hkv * hd), ly.dt(cfg)),
        "slot_pos": jnp.full((n_sites, Smax), -(2**30), jnp.int32),
        "pos": jnp.int32(0),
    }


def prefill(params, cfg: ModelConfig, batch, max_seq: int | None = None):
    """Python-loop prefill (keeps per-site cache extraction simple)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_seq = max_seq or S
    Smax = min(max_seq, cfg.local_window)
    x = ly.embed(params["embedding"], cfg, tokens)
    shared = params["shared"]
    mamba_states, cks, cvs, sps = [], [], [], []
    positions = jnp.arange(S)[None, :].astype(jnp.int32)
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        h = ly.rmsnorm(p["ln"], x)
        out, st = ssm.mamba2_block(p["mamba"], cfg, h)
        mamba_states.append(st)
        x = x + out
        if cfg.attn_every > 0 and (i + 1) % cfg.attn_every == 0:
            h = ly.rmsnorm(shared["ln1"], x)
            q, k, v = ly._project_qkv(shared["attn"], cfg, h, positions)
            attn = ly.chunked_attention(
                cfg, q, k, v, causal=True, window=cfg.local_window, softcap=None
            )
            x = x + attn.reshape(B, S, -1) @ shared["attn"]["wo"]
            ck, cv, sp = ly.fill_cache_from_prefill(k, v, Smax)
            cks.append(ck), cvs.append(cv), sps.append(sp)
            h = ly.rmsnorm(shared["ln2"], x)
            x = x + ly.mlp(shared["mlp"], cfg, h)
    x = ly.rmsnorm(params["ln_f"], x)
    last = ly.logits(params["embedding"], cfg, x[:, -1:])
    cache = {
        "mamba": jax.tree.map(lambda *s: jnp.stack(s), *mamba_states),
        "k": jnp.stack(cks),
        "v": jnp.stack(cvs),
        "slot_pos": jnp.stack(sps),
        "pos": jnp.int32(S),
    }
    return last, cache


def decode_step(params, cfg: ModelConfig, token, cache):
    x = ly.embed(params["embedding"], cfg, token)
    flags, slots, n_sites = _attn_flags(cfg)
    shared = params["shared"]
    pos = cache["pos"]

    def body(carry, inp):
        x, kc, vc, spc = carry
        p, st, flag, slot = inp
        h = ly.rmsnorm(p["ln"], x)
        out, st2 = ssm.mamba2_decode_step(p["mamba"], cfg, h, st)
        x = x + out

        def with_attn(args):
            x, kc, vc, spc = args
            h = ly.rmsnorm(shared["ln1"], x)
            out, ck, cv, sp = ly.decode_attention(
                shared["attn"], cfg, h, kc[slot], vc[slot], spc[slot], pos,
                window=cfg.local_window,
            )
            x = x + out
            h = ly.rmsnorm(shared["ln2"], x)
            x = x + ly.mlp(shared["mlp"], cfg, h)
            kc = jax.lax.dynamic_update_index_in_dim(kc, ck, slot, 0)
            vc = jax.lax.dynamic_update_index_in_dim(vc, cv, slot, 0)
            spc = jax.lax.dynamic_update_index_in_dim(spc, sp, slot, 0)
            return x, kc, vc, spc

        x, kc, vc, spc = jax.lax.cond(
            flag > 0, with_attn, lambda a: a, (x, kc, vc, spc)
        )
        return (x, kc, vc, spc), st2

    (x, kc, vc, spc), mamba_new = jax.lax.scan(
        body,
        (x, cache["k"], cache["v"], cache["slot_pos"]),
        (params["layers"], cache["mamba"], flags, slots),
        unroll=cfg.scan_unroll,
    )
    x = ly.rmsnorm(params["ln_f"], x)
    lg = ly.logits(params["embedding"], cfg, x)
    new_cache = {
        "mamba": mamba_new, "k": kc, "v": vc, "slot_pos": spc, "pos": pos + 1,
    }
    return lg, new_cache


def cache_logical_axes(cfg: ModelConfig, B: int):
    kv = (None, *ly.decode_cache_axes(cfg, B))
    return {
        "mamba": (
            (None, "batch", None, "ff"),          # conv buffer (L, B, K-1, dconv)
            (None, "batch", "heads", None, None),  # S state (L, B, H, N, P)
            (None, "batch", "heads", None),        # n state (L, B, H, N)
        ),
        "k": kv, "v": kv, "slot_pos": (None, None), "pos": (),
    }
