"""Mixture-of-Experts MLP: token-choice top-k routing over the routed experts,
of which this chip holds a contiguous share, plus always-on shared experts.

Routing scores every one of ``cfg.n_experts`` routed experts, whichever a
chip holds (expert parallelism): softmax, or sigmoid with a correction bias
that takes part in the choice of the top k and not in their weights
(DeepSeek-V3 §2.1.2). The chosen weights are renormalised to sum to one
and scaled (``routed_scale``). The chip computes the part of the routed sum
that its experts ``[expert_first, expert_first + experts_held)`` give; what
the experts of other chips would add is left out, and the shared experts,
which every chip holds, are added once.

Two dispatches:

* training (:func:`moe_mlp`) — GShard-style static-shape capacity dispatch:
  per-row expert capacity C = ceil(cf · S · top_k / E); overflow tokens
  drop to the residual path; expert FFNs run as one batched einsum over
  (E, C) slots, grouped by batch row so the scatter stays shard-local;
* inference (:func:`moe_infer`) — dropless grouped dispatch: the (token,
  choice) pairs are sorted by held expert and each expert's rows run through
  ``jax.lax.ragged_dot`` (a grouped matmul; on the TPU its own kernel), so
  the routed work is that of the pairs that landed here. Shapes are static:
  the sorted rows are all B·S·top_k pairs, the pairs routed elsewhere last,
  past the groups the matmul computes. It also returns the tokens each held
  expert took, for the serving engine's routing record.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models.layers import _act, dt, init_dense, init_mlp, mlp, mlp_logical_axes, use_weight
from repro.models.sharding import constrain

#: ``jax.named_scope``s of routing and of the expert FFNs (routed and shared)
ROUTE_SCOPE = "moe.route"
EXPERTS_SCOPE = "moe.experts"


def init_moe(rng, cfg: ModelConfig):
    ks = jax.random.split(rng, 5)
    E, d, f = cfg.n_held, cfg.d_model, cfg.d_ff
    scale = 1.0 / math.sqrt(d)

    def expert_stack(key, d_in, d_out):
        return (jax.random.normal(key, (E, d_in, d_out), jnp.float32) * scale).astype(dt(cfg))

    p = {
        "router": init_dense(ks[0], d, cfg.n_experts, jnp.float32),
        "wi": expert_stack(ks[1], d, f),
        "wo": expert_stack(ks[2], f, d),
    }
    if cfg.glu:
        p["wg"] = expert_stack(ks[3], d, f)
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros((cfg.n_experts,), jnp.float32)
    if cfg.n_shared_experts:
        p["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.n_shared_experts * cfg.d_ff)
    return p


def moe_logical_axes(cfg: ModelConfig):
    p = {
        "router": ("embed", None),
        "wi": ("experts", "embed", "ff"),
        "wo": ("experts", "ff", "embed"),
    }
    if cfg.glu:
        p["wg"] = ("experts", "embed", "ff")
    if cfg.router_score == "sigmoid":
        p["router_bias"] = (None,)
    if cfg.n_shared_experts:
        p["shared"] = mlp_logical_axes(cfg)
    return p


def route(params, cfg: ModelConfig, x: jax.Array):
    """x (..., d) → (scores (..., n_experts) f32, weights (..., k) f32,
    chosen expert ids (..., k) int32 over all routed experts)."""
    with jax.named_scope(ROUTE_SCOPE):
        gates = jnp.matmul(x.astype(jnp.float32), params["router"],
                           precision=jax.lax.Precision.HIGHEST)
        if cfg.router_score == "sigmoid":
            scores = jax.nn.sigmoid(gates)
            _, topi = jax.lax.top_k(scores + params["router_bias"], cfg.top_k)
            topw = jnp.take_along_axis(scores, topi, axis=-1)
        else:
            scores = jax.nn.softmax(gates, axis=-1)
            topw, topi = jax.lax.top_k(scores, cfg.top_k)
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
        if cfg.routed_scale != 1.0:
            topw = topw * cfg.routed_scale
    return scores, topw, topi


def _held(cfg: ModelConfig, topi: jax.Array):
    """(held expert index of each choice, clipped into range; is it held here)."""
    local = topi - cfg.expert_first
    held = (local >= 0) & (local < cfg.n_held)
    return jnp.where(held, local, 0), held


def _shared(params, cfg: ModelConfig, x: jax.Array) -> jax.Array | None:
    if not cfg.n_shared_experts:
        return None
    with jax.named_scope(EXPERTS_SCOPE):
        return mlp(params["shared"], cfg, x)


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


def moe_mlp(params, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Training: x (B, S, d) → (B, S, d), aux loss. Capacity dispatch per
    batch row over the held experts; choices of experts held elsewhere and
    overflow past the capacity add nothing."""
    B, S, d = x.shape
    E, K = cfg.n_held, cfg.top_k
    C = max(1, int(math.ceil(cfg.capacity_factor * S * K / E)))

    probs, topw, topi = route(params, cfg, x)  # (B, S, E_all), (B, S, K)

    # Aux load-balancing loss (GShard §2.2): E · Σ_e f_e · p̄_e.
    E_all = cfg.n_experts
    me = jnp.mean(probs, axis=(0, 1))  # (E_all,)
    assign = jax.nn.one_hot(topi[..., 0], E_all, dtype=jnp.float32)
    fe = jnp.mean(assign, axis=(0, 1))
    aux = E_all * jnp.sum(fe * me)

    local, held = _held(cfg, topi)
    # Position of each (token, choice) within its expert, per batch row.
    flat_i = local.reshape(B, S * K)  # (B, T') with T' = S·K
    flat_held = held.reshape(B, S * K)
    onehot = jax.nn.one_hot(flat_i, E, dtype=jnp.int32) * flat_held[..., None]
    pos = jnp.cumsum(onehot, axis=1) - 1  # (B, T', E)
    pos_in_e = jnp.take_along_axis(pos, flat_i[..., None], axis=2)[..., 0]  # (B, T')
    keep = (pos_in_e < C) & flat_held
    slot = jnp.where(keep, pos_in_e, C)  # overflow slot C is discarded

    with jax.named_scope(EXPERTS_SCOPE):
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
    shared = _shared(params, cfg, x)
    if shared is not None:
        out = out + shared
    return constrain(out, "batch", None, None), aux


def moe_infer(params, cfg: ModelConfig, x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Inference: x (B, S, d) → ((B, S, d), tokens (n_held + 1,) int32).

    Dropless: every (token, choice) pair whose expert is held here is
    computed, so a decode step (S = 1) reproduces a prefill's routing.
    ``tokens[e]`` counts the pairs held expert e took; ``tokens[-1]`` those
    routed to experts held elsewhere.
    """
    B, S, d = x.shape
    E, K = cfg.n_held, cfg.top_k
    T = B * S
    xt = x.reshape(T, d)
    _, topw, topi = route(params, cfg, xt)  # (T, K)
    local, held = _held(cfg, topi)
    group = jnp.where(held, local, E).reshape(T * K)  # E: routed elsewhere
    tokens = jnp.zeros((E + 1,), jnp.int32).at[group].add(1)
    with jax.named_scope(EXPERTS_SCOPE):
        order = jnp.argsort(group, stable=True)  # pairs grouped by held expert
        xs = xt[order // K]  # (T·K, d)
        sizes = tokens[:E]  # rows past their sum come out unspecified
        h = jax.lax.ragged_dot(xs, params["wi"], sizes, preferred_element_type=jnp.float32)
        if cfg.glu:
            g = jax.lax.ragged_dot(xs, params["wg"], sizes,
                                   preferred_element_type=jnp.float32)
            h = _act(cfg, g) * h
        else:
            h = _act(cfg, h)
        y = jax.lax.ragged_dot(h.astype(x.dtype), params["wo"], sizes,
                               preferred_element_type=jnp.float32)
        # back to (token, choice) order, weighted, summed over the choices;
        # a pair held elsewhere is selected away, not multiplied by zero,
        # since its unspecified row may hold NaN
        y = jnp.where(held.reshape(T * K, 1), y[jnp.argsort(order)] * topw.reshape(T * K, 1),
                      0.0)
        out = y.reshape(T, K, d).sum(axis=1).astype(x.dtype).reshape(B, S, d)
    shared = _shared(params, cfg, x)
    if shared is not None:
        out = out + shared
    return out, tokens
