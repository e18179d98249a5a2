"""Serving engine: storage-fed prompts → prefill → batched decode, through
the one served path, ``ClosedLoopServer.serve_round``."""

import numpy as np
import pytest

from repro.coding.codec import Codec
from repro.coding.layout import SharedKeyLayout
from repro.core import StaticPolicy
from repro.models import get
from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
from repro.storage import FaultyStore, MemoryStore, Proxy

import jax
import jax.numpy as jnp

from serve_helpers import closed_loop, greedy_reference, prompt_class


def _prompts(arch, rows, plen, seed):
    return np.random.default_rng(seed).integers(0, arch.cfg.vocab, (rows, plen)).astype(np.int32)


def test_generate_shapes_and_determinism():
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(0))
    with closed_loop(arch, params, _prompts(arch, 3, 8, 0), max_seq=64) as (srv, keys):
        out1 = srv.serve_round(keys, steps=5)
        out2 = srv.serve_round(keys, steps=5)
    assert out1.tokens.shape == (3, 5)
    np.testing.assert_array_equal(out1.tokens, out2.tokens)


def test_serve_via_erasure_coded_prompt_storage():
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(1))
    truth = _prompts(arch, 3, 16, 2)
    with closed_loop(arch, params, truth, max_seq=64,
                     read_policy=StaticPolicy(4, 2)) as (srv, keys):
        res = srv.serve_round(keys, steps=4)
    assert res.tokens.shape == (3, 4)
    assert res.ok == [True] * 3 and res.served_keys == keys
    assert all(c == (4, 2) for c in res.codes)
    np.testing.assert_array_equal(np.asarray(res.prompts)[:3], truth)
    # Cross-check: plain generation from the ground-truth prompts.
    np.testing.assert_array_equal(res.tokens,
                                  greedy_reference(arch, params, truth, 4, max_seq=64))


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
    """The round's tokens, decoded with the K/V cache donated step to step,
    equal an undonated ``arch.prefill`` + ``arch.decode_step`` loop."""
    arch = get(name, smoke=True)
    params = arch.init(jax.random.key(4))
    prompts = _prompts(arch, 3, 12, 4)
    steps = 12  # mixtral's window of 8 slots wraps
    with closed_loop(arch, params, prompts, max_seq=32) as (srv, keys):
        res = srv.serve_round(keys, steps=steps)
    np.testing.assert_array_equal(res.tokens,
                                  greedy_reference(arch, params, prompts, steps, max_seq=32))


def test_closed_loop_moe_round_keeps_routing_counters_readable():
    """A closed-loop round of an MoE model donates each step's K/V cache to
    the next step, and reads every step's routing counters after its loop."""
    from repro.serve import ROUTING

    arch = get("mixtral-8x7b", smoke=True)
    cfg = arch.cfg
    params = arch.init(jax.random.key(5))
    plen, steps = 16, 12
    n0 = len(ROUTING.rounds)
    with closed_loop(arch, params, _prompts(arch, 4, plen, 5), max_seq=24) as (srv, keys):
        res = srv.serve_round(keys, steps=steps)
        again = srv.serve_round(keys, steps=steps)
    np.testing.assert_array_equal(res.tokens, again.tokens)
    np.testing.assert_array_equal(
        res.tokens, greedy_reference(arch, params, np.asarray(res.prompts)[:4], steps,
                                     max_seq=24))
    rounds = list(ROUTING.rounds)[n0:]
    assert len(rounds) == 2
    for _, pre, dec in rounds:
        assert pre.shape == (cfg.n_moe_layers, cfg.n_experts + 1)
        assert (pre.sum(axis=1) == 4 * plen * cfg.top_k).all()
        assert dec.shape == (steps - 1, cfg.n_moe_layers, cfg.n_experts + 1)
        assert (dec.sum(axis=2) == 4 * cfg.top_k).all()


def test_round_drops_reads_that_failed_beyond_n_minus_k():
    """A prompt whose every chunk read fails leaves the round: ``ok`` masks
    it, and ``served_keys``, ``tokens`` and ``prompts`` hold the served rows
    in order."""
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(6))
    truth = _prompts(arch, 4, 16, 6)
    faulty = []

    def lossy(inner):
        faulty.append(FaultyStore(inner))
        return faulty[0]

    with closed_loop(arch, params, truth, max_seq=32, store=lossy) as (srv, keys):
        faulty[0].lose_object(keys[1])
        faulty[0].lose_object(keys[3])
        res = srv.serve_round(keys, steps=3)
    assert res.ok == [True, False, True, False]
    assert res.served_keys == [keys[0], keys[2]]
    assert res.tokens.shape == (2, 3) and len(res.codes) == 2
    np.testing.assert_array_equal(np.asarray(res.prompts)[:2], truth[[0, 2]])
    np.testing.assert_array_equal(
        res.tokens, greedy_reference(arch, params, truth[[0, 2]], 3, max_seq=32))


def test_round_with_every_read_failed_raises():
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(7))
    with closed_loop(arch, params, _prompts(arch, 2, 16, 7), max_seq=32,
                     store=lambda inner: FaultyStore(inner, p_fail=1.0)) as (srv, keys):
        with pytest.raises(RuntimeError, match="all 2 prompt fetches failed"):
            srv.serve_round(keys, steps=2)
    assert srv.traces == 0  # nothing was launched


def test_server_rejects_prompts_longer_than_the_layout():
    arch = get("qwen1.5-0.5b", smoke=True)
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=16)  # 64 bytes: 16 tokens
    step = FusedServingStep.for_policy(ServePolicy.tofec(), prompt_class(layout), 16,
                                       codec=Codec("jnp"))
    proxy = Proxy(MemoryStore(), StaticPolicy(8, 4), L=8)
    try:
        eng = ServingEngine(arch, arch.init(jax.random.key(8)), max_seq=32)
        ClosedLoopServer(eng, proxy, layout, step, prompt_len=16)
        with pytest.raises(ValueError, match="prompt_len 17 needs 68 bytes"):
            ClosedLoopServer(eng, proxy, layout, step, prompt_len=17)
    finally:
        proxy.close()


def test_padding_rows_do_not_leak_into_served_tokens():
    """A round of 3 runs at the padded bucket batch 4: its requests' greedy
    tokens equal theirs in a full round of 4."""
    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(9))
    with closed_loop(arch, params, _prompts(arch, 4, 16, 9), max_seq=32) as (srv, keys):
        three = srv.serve_round(keys[:3], steps=6)
        four = srv.serve_round(keys, steps=6)
    assert srv.traces == 1  # 3 and 4 share the batch bucket 4
    assert three.tokens.shape == (3, 6)
    np.testing.assert_array_equal(three.tokens, four.tokens[:3])
