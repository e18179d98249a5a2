"""Mixture-of-Experts MLP: token-choice top-k routing with per-row capacity.

GShard-style static-shape dispatch adapted to TPU/GSPMD:
  * tokens are grouped by batch row (the data-sharded axis), so the
    dispatch scatter and combine gather stay shard-local under pjit;
  * per-row expert capacity C = ceil(cf · S · top_k / E); overflow tokens
    drop to the residual path (standard capacity-based dropping);
  * expert FFNs run as one batched einsum over (E, C) slots with d_ff
    sharded over the "model" axis (TP-within-expert — E=8 does not divide
    the 16-way model axis, see DESIGN.md §5).

Returns (output, aux_load_balance_loss).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _act, dt, init_dense, use_weight
from repro.models.sharding import constrain


def init_moe(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 4)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(d)

    def expert_stack(key, d_in, d_out):
        return (jax.random.normal(key, (E, d_in, d_out), jnp.float32) * scale).astype(dt(cfg))

    p = {
        "router": init_dense(ks[0], d, E, jnp.float32),
        "wi": expert_stack(ks[1], d, f),
        "wo": expert_stack(ks[2], f, d),
    }
    if cfg.glu:
        p["wg"] = expert_stack(ks[3], d, f)
    return p


def moe_logical_axes(cfg: ModelConfig):
    p = {
        "router": ("embed", None),
        "wi": ("experts", "embed", "ff"),
        "wo": ("experts", "ff", "embed"),
    }
    if cfg.glu:
        p["wg"] = ("experts", "embed", "ff")
    return p


def _expert_einsum(spec: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """Expert contraction: operands in cfg.dtype, fp32 accumulation.

    XLA:CPU has no batched BF16 x BF16 = F32 dot, so the CPU lowering upcasts
    the operands first (exact: bf16 values and their products fit fp32).
    Every other platform lowers the contraction exactly as written.
    """

    def native(x, w):
        return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)

    def cpu(x, w):
        return native(x.astype(jnp.float32), w.astype(jnp.float32))

    return jax.lax.platform_dependent(x, w, cpu=cpu, default=native)


def moe_mlp(
    params, cfg: ModelConfig, x: jax.Array, *, dropless: bool = False
) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (B, S, d), aux loss. Dispatch is per batch row.

    ``dropless=True`` sizes per-row capacity at its tight upper bound C = S
    (a token contributes each expert at most once), so no token is ever
    dropped. Inference paths (prefill / decode) use this: capacity dropping
    is a *training-time* load-balancing economy, and at S=1 a decode step
    can never drop — so prefill must not drop either, or teacher-forcing
    decode-vs-prefill parity breaks on exactly the overflowed tokens.

    Cost note: dropless dispatch buffers are (B, E, S, d) — roughly
    E/(K·capacity_factor) × the capacity-bounded path — so long-context
    prefill pays dense worst-case slots for a sparse dispatch. A
    sort/segment-based dropless dispatch removes that overhead (ROADMAP
    open item); at decode (S=1) the two paths cost the same.
    """
    B, S, d = x.shape
    E, K = cfg.n_experts, cfg.top_k
    if dropless:
        C = S
    else:
        C = max(1, int(math.ceil(cfg.capacity_factor * S * K / E)))

    gates = (x.astype(jnp.float32) @ params["router"])  # (B, S, E)
    probs = jax.nn.softmax(gates, axis=-1)
    topw, topi = jax.lax.top_k(probs, K)  # (B, S, K)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # Aux load-balancing loss (GShard §2.2): E · Σ_e f_e · p̄_e.
    me = jnp.mean(probs, axis=(0, 1))  # (E,)
    assign = jax.nn.one_hot(topi[..., 0], E, dtype=jnp.float32)
    fe = jnp.mean(assign, axis=(0, 1))
    aux = E * jnp.sum(fe * me)

    # Position of each (token, choice) within its expert, per batch row.
    flat_i = topi.reshape(B, S * K)  # (B, T') with T' = S·K
    onehot = jax.nn.one_hot(flat_i, E, dtype=jnp.int32)  # (B, T', E)
    pos = jnp.cumsum(onehot, axis=1) - 1  # (B, T', E)
    pos_in_e = jnp.take_along_axis(pos, flat_i[..., None], axis=2)[..., 0]  # (B, T')
    keep = pos_in_e < C
    slot = jnp.where(keep, pos_in_e, C)  # overflow slot C is discarded

    # Dispatch: scatter tokens into (B, E, C+1, d) slots (row-local).
    xt = jnp.repeat(x, K, axis=1)  # (B, T', d) token repeated per choice
    b_idx = jnp.arange(B)[:, None] * jnp.ones_like(flat_i)
    buf = jnp.zeros((B, E, C + 1, d), x.dtype)
    buf = buf.at[b_idx, flat_i, slot].add(xt)
    buf = buf[:, :, :C]  # (B, E, C, d)
    buf = constrain(buf, "batch", "experts", None, None)

    # Expert FFN over slots; d_ff TP-sharded over "model". Contractions
    # accumulate in fp32 (MXU-native); operands stay in cfg.dtype.
    wi = use_weight(cfg, params["wi"], None, None, "ff")
    h = _expert_einsum("becd,edf->becf", buf, wi)
    if cfg.glu:
        wg = use_weight(cfg, params["wg"], None, None, "ff")
        g = _expert_einsum("becd,edf->becf", buf, wg)
        h = _act(cfg, g) * h
    else:
        h = _act(cfg, h)
    h = constrain(h, "batch", "experts", None, "ff").astype(x.dtype)
    wo = use_weight(cfg, params["wo"], None, "ff", None)
    y = _expert_einsum("becf,efd->becd", h, wo)

    # Combine in fp32: gather each choice's slot, weight, sum over K.
    y = jnp.concatenate([y, jnp.zeros((B, E, 1, d), y.dtype)], axis=2)
    yt = y[b_idx, flat_i, slot]  # (B, T', d) fp32
    yt = yt * (topw.reshape(B, S * K)[..., None] * keep[..., None])
    out = yt.reshape(B, S, K, d).sum(axis=2).astype(x.dtype)
    return constrain(out, "batch", None, None), aux
