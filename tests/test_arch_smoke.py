"""Per-architecture smoke tests: reduced configs, one forward/train step on
CPU, asserting output shapes and no NaNs; plus a prefill→decode consistency
check per family."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import arch_names, get
from repro.models.config import ShapeSpec
from repro.models.registry import make_batch

SMOKE_SHAPE = ShapeSpec("smoke_train", "train", seq=32, batch=2)


@pytest.mark.parametrize("name", arch_names())
def test_smoke_train_step(name):
    arch = get(name, smoke=True)
    params = arch.init(jax.random.key(0))
    batch = make_batch(arch.cfg, SMOKE_SHAPE)

    loss, grads = jax.jit(jax.value_and_grad(lambda p: arch.train_loss(p, batch)))(params)
    assert np.isfinite(float(loss)), f"{name}: loss not finite"
    # Loss at init should be near ln(vocab) for random labels.
    assert 0.2 * np.log(arch.cfg.vocab) < float(loss) < 3.0 * np.log(arch.cfg.vocab)
    leaf_norms = [float(jnp.linalg.norm(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)]
    assert all(np.isfinite(leaf_norms)), f"{name}: non-finite grads"
    assert any(n > 0 for n in leaf_norms), f"{name}: all-zero grads"


@pytest.mark.parametrize("name", arch_names())
def test_smoke_prefill_decode(name):
    arch = get(name, smoke=True)
    params = arch.init(jax.random.key(1))
    B, S = 2, 16
    shape = ShapeSpec("smoke_prefill", "prefill", seq=S, batch=B)
    batch = make_batch(arch.cfg, shape)

    last, cache = jax.jit(lambda p, b: arch.prefill(p, b, max_seq=S + 8))(params, batch)
    assert last.shape == (B, 1, arch.cfg.vocab)
    assert np.all(np.isfinite(np.asarray(last)))

    token = jnp.argmax(last, axis=-1).astype(jnp.int32)
    logits2, cache2 = jax.jit(arch.decode_step)(params, token, cache)
    assert logits2.shape == (B, 1, arch.cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits2)))
    # One more step to exercise cache advancement.
    token3 = jnp.argmax(logits2, axis=-1).astype(jnp.int32)
    logits3, _ = jax.jit(arch.decode_step)(params, token3, cache2)
    assert np.all(np.isfinite(np.asarray(logits3)))


@pytest.mark.parametrize(
    "name,steps",
    [("mistral-nemo-12b", 1), ("mixtral-8x7b", 1), ("zamba2-2.7b", 1), ("xlstm-350m", 1),
     # the serving cell's architecture; gemma2's local/global layers (window 8)
     ("qwen1.5-0.5b", 4), ("gemma2-2b", 4),
     # a sliding window of 8: the ring of 8 slots wraps during the decode
     ("mixtral-8x7b", 10)],
    ids=["mistral-nemo-12b", "mixtral-8x7b", "zamba2-2.7b", "xlstm-350m",
         "qwen1.5-0.5b", "gemma2-2b", "mixtral-8x7b-ring"],
)
def test_decode_matches_prefill_continuation(name, steps):
    """Decoding token t+1 after prefill[0:t] must match prefill[0:t+1]'s
    last logits (teacher-forcing consistency), at every one of ``steps``
    cached steps."""
    arch = get(name, smoke=True)
    params = arch.init(jax.random.key(2))
    rng = np.random.default_rng(3)
    B, S = 2, 12
    toks = rng.integers(0, arch.cfg.vocab, size=(B, S + steps))
    extra = {}
    if arch.cfg.family == "vlm":
        extra["patches"] = jnp.asarray(
            rng.normal(size=(B, arch.cfg.vision_patches, arch.cfg.d_model)), jnp.float32)
    max_seq = S + max(steps, 4)
    prefill = jax.jit(arch.prefill, static_argnames="max_seq")
    decode = jax.jit(arch.decode_step)

    _, cache = prefill(params, {"tokens": jnp.asarray(toks[:, :S], jnp.int32), **extra},
                       max_seq=max_seq)
    for t in range(S, S + steps):
        step_logits, cache = decode(params, jnp.asarray(toks[:, t : t + 1], jnp.int32), cache)
        full_logits, _ = prefill(
            params, {"tokens": jnp.asarray(toks[:, : t + 1], jnp.int32), **extra},
            max_seq=max_seq)
        np.testing.assert_allclose(
            np.asarray(step_logits), np.asarray(full_logits), rtol=0.08, atol=0.08,
            err_msg=f"{name}: decode step {t - S}",
        )


@pytest.mark.parametrize("name,window", [("qwen1.5-0.5b", None), ("mixtral-8x7b", 5),
                                         ("gemma2-2b", 0), ("gemma2-2b", 5)])
def test_decode_attend_matches_per_head_attention(name, window):
    """The block-diagonal head products over a (B, Smax, Hkv·hd) cache row
    equal plain per-head attention (the step's formulation before it), in
    float32: ring slots, empty slots, the window (0 = full), grouped
    queries and gemma2's score soft-cap."""
    from repro.models import layers as ly

    cfg = dataclasses.replace(get(name, smoke=True).cfg, dtype="float32")
    B, Smax, Hkv, G, hd = 2, 8, cfg.n_kv_heads, cfg.q_per_kv, cfg.hd
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, 1, Hkv * G, hd)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, Smax, Hkv, hd)), jnp.float32) for _ in "kv")
    pos = 13  # slots hold positions 6..13 in ring order, one slot left empty
    slot_pos = jnp.asarray([8, 9, 10, 11, 12, 13, -(2**30), 7], jnp.int32)
    wo = jnp.asarray(rng.normal(size=(Hkv * G * hd, cfg.d_model)), jnp.float32)

    got = ly.decode_attend({"wo": wo}, cfg, q, k.reshape(B, Smax, -1), v.reshape(B, Smax, -1),
                           slot_pos, jnp.int32(pos), window=window)

    s = jnp.einsum("bhgd,bkhd->bhgk", q.reshape(B, Hkv, G, hd), k) / np.sqrt(hd)
    if cfg.attn_softcap:
        s = cfg.attn_softcap * jnp.tanh(s / cfg.attn_softcap)
    live = (slot_pos >= 0) & (slot_pos <= pos)
    if window:
        live &= slot_pos > pos - window
    p = jax.nn.softmax(jnp.where(live, s, -1e30), axis=-1)
    want = jnp.einsum("bhgk,bkhd->bhgd", p, v).reshape(B, 1, -1) @ wo
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_param_count_estimates():
    """Analytic N vs actual init leaf count — within 10% for the big dense
    archs (validates MODEL_FLOPS = 6·N·D inputs)."""
    for name in ["yi-6b", "mistral-nemo-12b", "mixtral-8x7b"]:
        arch = get(name, smoke=False)
        est = arch.cfg.param_count_dense()
        want = {"yi-6b": 6e9, "mistral-nemo-12b": 12e9, "mixtral-8x7b": 46e9}[name]
        assert 0.7 * want < est < 1.4 * want, (name, est)
