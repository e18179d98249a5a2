"""Serving engine: storage-fed prompts → prefill → batched decode."""

import numpy as np
import pytest

from repro.coding.layout import SharedKeyLayout
from repro.core import StaticPolicy
from repro.models import get
from repro.serve import ServingEngine
from repro.storage import MemoryStore, Proxy

import jax
import jax.numpy as jnp


def test_generate_shapes_and_determinism():
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(0))
    eng = ServingEngine(arch, params, max_seq=64)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, arch.cfg.vocab, size=(3, 8)).astype(np.int32)
    out1 = eng.generate(prompts, steps=5)
    out2 = eng.generate(prompts, steps=5)
    assert out1.shape == (3, 5)
    np.testing.assert_array_equal(out1, out2)


def test_serve_via_erasure_coded_prompt_storage():
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(1))
    eng = ServingEngine(arch, params, max_seq=64)

    prompt_len = 16
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)  # 4·16B strips
    store = MemoryStore()
    rng = np.random.default_rng(2)
    keys = []
    truth = []
    for i in range(3):
        toks = rng.integers(0, arch.cfg.vocab, size=(prompt_len,)).astype(np.int32)
        key = f"prompt/{i}"
        ServingEngine.store_prompt(store, key, layout, toks)
        keys.append(key)
        truth.append(toks)

    proxy = Proxy(store, StaticPolicy(4, 2), L=8)
    try:
        res = eng.serve(proxy, layout, keys, prompt_len=prompt_len, steps=4)
        assert res.tokens.shape == (3, 4)
        assert all(c == (4, 2) for c in res.codes)
        # Cross-check: direct generation from the ground-truth prompts.
        direct = eng.generate(np.stack(truth), steps=4)
        np.testing.assert_array_equal(res.tokens, direct)
    finally:
        proxy.close()


def test_decode_step_donates_only_the_kv_cache():
    """The engine's decode step takes the K/V cache's buffers for its output;
    the positions and an MoE model's routing counters stay readable."""
    arch = get("mixtral-8x7b", smoke=True)
    params = arch.init(jax.random.key(3))
    eng = ServingEngine(arch, params, max_seq=24)
    prompts = np.random.default_rng(3).integers(0, arch.cfg.vocab, (2, 8)).astype(np.int32)
    logits, cache = eng._prefill(params, {"tokens": jnp.asarray(prompts)})
    tok = jnp.argmax(logits, axis=-1).astype(np.int32)
    _, new = eng._decode(params, tok, cache)
    assert cache["k"].is_deleted() and cache["v"].is_deleted()
    for name in ("slot_pos", "pos", "expert_tokens"):
        assert not cache[name].is_deleted(), name
    assert int(cache["expert_tokens"].sum()) == arch.cfg.n_moe_layers * 2 * 8 * arch.cfg.top_k
    assert int(new["expert_tokens"].sum()) == arch.cfg.n_moe_layers * 2 * arch.cfg.top_k


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "mixtral-8x7b"])
def test_generate_matches_an_undonated_decode_loop(name):
    arch = get(name, smoke=True)
    params = arch.init(jax.random.key(4))
    eng = ServingEngine(arch, params, max_seq=32)
    prompts = np.random.default_rng(4).integers(0, arch.cfg.vocab, (3, 12)).astype(np.int32)
    steps = 12  # mixtral's window of 8 slots wraps
    logits, cache = jax.jit(lambda p, b: arch.prefill(p, b, max_seq=32))(
        params, {"tokens": jnp.asarray(prompts)})
    decode = jax.jit(arch.decode_step)
    want = []
    for _ in range(steps):
        tok = jnp.argmax(logits, axis=-1).astype(np.int32)
        want.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, tok, cache)
    np.testing.assert_array_equal(eng.generate(prompts, steps), np.stack(want, axis=1))


def test_closed_loop_moe_round_keeps_routing_counters_readable():
    """A closed-loop round of an MoE model donates each step's K/V cache to
    the next step, and reads every step's routing counters after its loop."""
    from repro.coding.codec import Codec
    from repro.core import PAPER_READ_3MB, RequestClass
    from repro.serve import ROUTING, ClosedLoopServer, FusedServingStep, ServePolicy

    arch = get("mixtral-8x7b", smoke=True)
    cfg = arch.cfg
    eng = ServingEngine(arch, arch.init(jax.random.key(5)), max_seq=24)
    plen, steps, keys = 16, 12, []
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=plen)
    store = MemoryStore()
    rng = np.random.default_rng(5)
    for i in range(4):
        ServingEngine.store_prompt(store, f"p/{i}", layout,
                                   rng.integers(0, cfg.vocab, (plen,)).astype(np.int32))
        keys.append(f"p/{i}")
    cls = RequestClass("prompt", layout.file_bytes / 2**20, PAPER_READ_3MB,
                       k_max=4, r_max=2.0, n_max=8)
    proxy = Proxy(store, StaticPolicy(8, 4), L=8)
    step = FusedServingStep.for_policy(ServePolicy.tofec(), cls, 16, codec=Codec("jnp"))
    srv = ClosedLoopServer(eng, proxy, layout, step, prompt_len=plen)
    n0 = len(ROUTING.rounds)
    try:
        res = srv.serve_round(keys, steps=steps)
        again = srv.serve_round(keys, steps=steps)
    finally:
        proxy.close()
    np.testing.assert_array_equal(res.tokens, again.tokens)
    np.testing.assert_array_equal(res.tokens, eng.generate(np.asarray(res.prompts)[:4], steps))
    rounds = list(ROUTING.rounds)[n0:]
    assert len(rounds) == 2
    for _, pre, dec in rounds:
        assert pre.shape == (cfg.n_moe_layers, cfg.n_experts + 1)
        assert (pre.sum(axis=1) == 4 * plen * cfg.top_k).all()
        assert dec.shape == (steps - 1, cfg.n_moe_layers, cfg.n_experts + 1)
        assert (dec.sum(axis=2) == 4 * cfg.top_k).all()
