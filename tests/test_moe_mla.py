"""The expert layer that holds one chip's share and the latent attention, at a
smoke size on the CPU: shares of the routed experts add up to the uncut
layer, the grouped inference dispatch agrees with the training dispatch,
absorbed latent decode equals the decompressed form, and the closed-loop
server compiles once per bucket and records each round's routing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import get, lm, mla, moe
from repro.models.registry import Arch

F32 = dict(dtype="float32")


@pytest.fixture(scope="module")
def moon():
    """The moonlight smoke config at float32 (8 routed experts, 2 chosen,
    2 shared, one leading dense layer) and its MoE stack's first layer."""
    cfg = dataclasses.replace(get("moonlight-16b-a3b", smoke=True).cfg, **F32)
    params = lm.init(jax.random.key(0), cfg)
    layer = jax.tree.map(lambda a: a[0], params["layers"])
    # a drawn correction bias, so that it takes part in the choice
    layer["moe"]["router_bias"] = 0.3 * jax.random.normal(jax.random.key(1), (cfg.n_experts,))
    return cfg, params, layer


def _x(cfg, B=3, S=7, seed=2):
    return jax.random.normal(jax.random.key(seed), (B, S, cfg.d_model), jnp.float32)


def test_expert_shares_sum_to_the_uncut_layer(moon):
    """Four chips of 2 of the 8 routed experts each: their outputs, with the
    shared experts (which every chip computes alike) counted once, add up to
    what the layer that holds all 8 gives; their counters to its counters."""
    cfg, _, layer = moon
    p = layer["moe"]
    x = _x(cfg)
    whole, whole_tokens = moe.moe_infer(p, cfg, x)
    shared = moe.mlp(p["shared"], cfg, x)
    parts, held = [], 0
    for first in range(0, 8, 2):
        c = dataclasses.replace(cfg, expert_first=first, experts_held=2)
        ps = dict(p, **{k: p[k][first:first + 2] for k in ("wi", "wg", "wo")})
        out, tokens = moe.moe_infer(ps, c, x)
        parts.append(out - shared)
        held += int(tokens[:2].sum())
        # all pairs are counted on every chip: its own, then the rest
        assert int(tokens.sum()) == x.shape[0] * x.shape[1] * cfg.top_k
        np.testing.assert_array_equal(tokens[:2], whole_tokens[first:first + 2])
    np.testing.assert_allclose(sum(parts) + shared, whole, rtol=1e-5, atol=1e-5)
    assert held == int(whole_tokens[:-1].sum()) == x.shape[0] * x.shape[1] * cfg.top_k
    assert int(whole_tokens[-1]) == 0


def test_grouped_dispatch_matches_the_capacity_dispatch(moon):
    """At a capacity no token overflows, training's slot dispatch and the
    grouped inference dispatch compute the same layer, shares included."""
    cfg, _, layer = moon
    x = _x(cfg, seed=3)
    for c, p in [(cfg, layer["moe"]),
                 (dataclasses.replace(cfg, expert_first=4, experts_held=3),
                  dict(layer["moe"], **{k: layer["moe"][k][4:7] for k in ("wi", "wg", "wo")}))]:
        wide = dataclasses.replace(c, capacity_factor=float(c.n_experts))
        got, _ = moe.moe_infer(p, c, x)
        want, _ = moe.moe_mlp(p, wide, x)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_grouped_dispatch_ignores_the_rows_past_its_groups(moon, monkeypatch):
    """The grouped matmul leaves the rows past its groups unspecified (on the
    TPU they hold whatever was there): filled with NaN, the pairs routed to
    other chips' experts still add nothing to the output."""
    cfg, _, layer = moon
    c = dataclasses.replace(cfg, expert_first=4, experts_held=3)
    p = dict(layer["moe"], **{k: layer["moe"][k][4:7] for k in ("wi", "wg", "wo")})
    x = _x(cfg, seed=5)
    want, tokens = moe.moe_infer(p, c, x)
    assert int(tokens[-1]) > 0  # some pairs are routed elsewhere
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        out = real(lhs, rhs, group_sizes, **kw)
        rows = jnp.arange(out.shape[0])[:, None]
        return jnp.where(rows < group_sizes.sum(), out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got, _ = moe.moe_infer(p, c, x)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got, want)


def test_routing_weights_and_bias(moon):
    """Sigmoid scores: the bias moves the choice but not the weights, which
    are the chosen scores renormalised and scaled."""
    cfg, _, layer = moon
    p = layer["moe"]
    x = _x(cfg, seed=4).reshape(-1, cfg.d_model)
    scores, w, idx = moe.route(p, cfg, x)
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scale, rtol=1e-6)
    chosen = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(w, cfg.routed_scale * chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-6)
    _, _, idx0 = moe.route(dict(p, router_bias=jnp.zeros_like(p["router_bias"])), cfg, x)
    assert (np.asarray(idx0) != np.asarray(idx)).any()


def test_absorbed_latent_decode_equals_the_decompressed_form(moon):
    """Attending one new position through the cached latent, with the
    up-projections absorbed, gives the decompressed causal attention's
    output at that position."""
    cfg, _, layer = moon
    a = layer["attn"]
    B, S = 2, 9
    x = _x(cfg, B, S, seed=5)
    full, ckv, kpe = mla.attention(a, cfg, x)
    Smax = 12
    ckv_c = jnp.zeros((B, Smax, cfg.kv_lora_rank)).at[:, :S - 1].set(ckv[:, :S - 1])
    kpe_c = jnp.zeros((B, Smax, cfg.qk_rope_head_dim)).at[:, :S - 1].set(kpe[:, :S - 1])
    slot_pos = jnp.where(jnp.arange(Smax) < S - 1, jnp.arange(Smax), -(2**30))
    out, c_new, r_new = mla.decode_attention(a, cfg, x[:, S - 1:], ckv_c, kpe_c, slot_pos,
                                             jnp.int32(S - 1))
    np.testing.assert_allclose(out[:, 0], full[:, S - 1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(c_new[:, 0], ckv[:, S - 1], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(r_new[:, 0], kpe[:, S - 1], rtol=1e-5, atol=1e-6)


def test_latent_cache_decode_continues_the_prefill(moon):
    """Prefill of S tokens, then one decode step through the latent cache,
    gives the logits of a prefill of S + 1 tokens; the caches hold 576-wide
    style latents (kv_lora_rank + qk_rope_head_dim), not per-head K/V."""
    cfg, params, _ = moon
    arch = Arch(cfg=cfg, module=lm)
    toks = jnp.asarray(np.random.default_rng(6).integers(0, cfg.vocab, (2, 11)), jnp.int32)
    _, cache = arch.prefill(params, {"tokens": toks[:, :10]}, max_seq=16)
    assert set(cache) == {"ckv", "kpe", "slot_pos", "pos", "expert_tokens"}
    assert cache["ckv"].shape == (cfg.n_layers, 2, 16, cfg.kv_lora_rank)
    assert cache["kpe"].shape == (cfg.n_layers, 2, 16, cfg.qk_rope_head_dim)
    assert cache["expert_tokens"].shape == (cfg.n_moe_layers, cfg.n_held + 1)
    step, new = arch.decode_step(params, toks[:, 10:], cache)
    full, _ = arch.prefill(params, {"tokens": toks}, max_seq=16)
    np.testing.assert_allclose(step, full, rtol=1e-4, atol=1e-4)
    assert int(new["expert_tokens"].sum()) == 2 * cfg.top_k * cfg.n_moe_layers


def test_param_count_covers_latent_attention_and_shared_experts():
    cfg = get("moonlight-16b-a3b").cfg
    # 27 layers at d 2048: 26 MoE layers of 584.8 M, a dense one of 83.0 M,
    # embedding and head 671.1 M (the published 15.96 B)
    assert cfg.param_count_dense() == pytest.approx(15.96e9, rel=2e-3)
    # 6 of 64 routed experts a token: about 2.9 B active (the A3B)
    assert 2.7e9 < cfg.active_param_count() < 3.1e9


def test_closed_loop_moe_compiles_once_and_records_routing():
    """Moonlight's smoke config through ClosedLoopServer: one fused compile
    for every round of one bucket, one decode-step compile, and a routing
    record per round whose counters cover every (token, choice) pair."""
    from repro.coding.codec import Codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core import PAPER_READ_3MB, RequestClass, StaticPolicy
    from repro.serve import ROUTING, ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
    from repro.storage import MemoryStore, Proxy

    cfg = dataclasses.replace(get("moonlight-16b-a3b", smoke=True).cfg,
                              expert_first=2, experts_held=4)
    arch = Arch(cfg=cfg, module=lm)
    params = arch.init(jax.random.key(7))
    eng = ServingEngine(arch, params, max_seq=24)
    plen, steps, keys = 16, 3, []
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=plen)
    store = MemoryStore()
    rng = np.random.default_rng(8)
    for i in range(4):
        toks = rng.integers(0, cfg.vocab, (plen,)).astype(np.int32)
        ServingEngine.store_prompt(store, f"p/{i}", layout, toks)
        keys.append(f"p/{i}")
    cls = RequestClass("prompt", layout.file_bytes / 2**20, PAPER_READ_3MB,
                       k_max=4, r_max=2.0, n_max=8)
    proxy = Proxy(store, StaticPolicy(8, 4), L=8)
    step = FusedServingStep.for_policy(ServePolicy.tofec(), cls, 16, codec=Codec("jnp"))
    srv = ClosedLoopServer(eng, proxy, layout, step, prompt_len=plen)
    n0 = len(ROUTING.rounds)
    try:
        for _ in range(4):
            srv.serve_round(keys, steps=steps)
    finally:
        proxy.close()
    assert srv.traces == 1, f"{srv.traces} fused compiles for 4 rounds of one bucket"
    assert eng._decode_step._cache_size() == 1
    rounds = list(ROUTING.rounds)[n0:]
    assert len(rounds) == 4
    for _, pre, dec in rounds:
        assert pre.shape == (cfg.n_moe_layers, 5)
        assert (pre.sum(axis=1) == 4 * plen * cfg.top_k).all()
        # every decode step whose token was read back: steps - 1 of them
        assert dec.shape == (steps - 1, cfg.n_moe_layers, 5)
        assert (dec.sum(axis=2) == 4 * cfg.top_k).all()
