"""The benchmark's float32 reference forward agrees with the program's prefill
and with decoding through its cache, at a smoke size on the CPU."""

import bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import dense_lm


@pytest.fixture(scope="module")
def model():
    from repro.models import lm
    from repro.models.registry import Arch

    cfg, _ = bench_tiny.tiny(bench_tiny.PROMPT)
    s = dense_lm.sizes(cfg)
    return Arch(cfg=dense_lm.program_config(cfg), module=lm), s, dense_lm.init_params(7, s)


def test_program_config_is_the_published_one():
    cfg = bench_tiny.manifest.load_json(bench_tiny.BENCH / "configs" / "qwen1.5-0.5b.json")
    mc = dense_lm.program_config(cfg)
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.n_kv_heads, mc.d_ff, mc.vocab) == (
        24, 1024, 16, 16, 2816, 151936)
    assert mc.rope_theta == 1e6 and mc.qkv_bias and mc.dtype == "bfloat16"


def test_reference_agrees_with_prefill_and_cached_decode(model):
    arch, s, params = model
    rng = np.random.default_rng(0)
    B, S, steps = 3, 12, 5
    prompt = rng.integers(0, s["vocab"], (B, S), dtype=np.int32)
    logits, cache = jax.jit(lambda p, t: arch.prefill(p, {"tokens": t}, max_seq=S + steps))(
        params, jnp.asarray(prompt))
    got, toks = [np.asarray(logits)[:, 0]], []
    decode = jax.jit(arch.decode_step)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(steps - 1):
        toks.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, tok, cache)
        got.append(np.asarray(logits)[:, 0])
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    got = np.stack(got, 1)  # (B, steps, V)
    seqs = np.concatenate([prompt, np.stack(toks, 1)], 1)
    ref = dense_lm.reference_logits(params, s, seqs, np.arange(S - 1, S - 1 + steps), block=1)
    scale = np.abs(ref).max()
    # bf16 weights in both; the program keeps bf16 activations and rounds its
    # logits to bf16 (2**-8 relative), the reference computes in float32
    assert np.abs(got - ref).max() <= 0.03 * scale
    # and a wrong model does not: the control's fp8 weights move it further
    low = dense_lm.reference_logits(params, s, seqs, np.arange(S - 1, S - 1 + steps),
                                    control=True, block=1)
    assert np.abs(low - ref).max() > np.abs(got - ref).max()


def test_reference_sees_bias_norm_and_rope(model):
    """Each of these changes the reference's logits: none is silently unused."""
    _, s, params = model
    toks = np.random.default_rng(1).integers(0, s["vocab"], (1, 10), dtype=np.int32)
    pos = np.arange(10)
    base = dense_lm.reference_logits(params, s, toks, pos)
    for path in (("layers", "attn", "bq"), ("layers", "ln1", "scale"), ("ln_f", "scale")):
        p2 = jax.tree.map(lambda a: a, params)
        node = p2
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = jnp.zeros_like(node[path[-1]])
        assert np.abs(dense_lm.reference_logits(p2, s, toks, pos) - base).max() > 1e-3, path
    s2 = dict(s, rope_theta=10_000.0)
    assert np.abs(dense_lm.reference_logits(params, s2, toks, pos) - base).max() > 1e-3


def test_widest_gap():
    ref = np.array([[[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]]])
    assert dense_lm.widest_gap(ref, np.array([[1, 0]])) == 0.0
    assert dense_lm.widest_gap(ref, np.array([[2, 1]])) == 2.5
