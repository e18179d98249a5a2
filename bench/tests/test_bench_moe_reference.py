"""The benchmark's float32 reference of the latent-attention MoE model agrees
with the program's prefill and with decoding through its latent cache, on
the serving engine's path, at a smoke size on the CPU."""

import dataclasses

import bench_tiny
import bench_tiny_moe
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import mla_moe_lm

B, S, STEPS = 3, 12, 5


@pytest.fixture(scope="module")
def model():
    cfg = bench_tiny_moe.tiny()
    s = mla_moe_lm.sizes(cfg)
    return cfg, s, mla_moe_lm.init_params(7, s)


def _served_logits(cfg, s, params, dtype):
    """Logits (B, STEPS, V) of a prefill and STEPS - 1 greedy decode steps
    through ServingEngine's jitted prefill and decode step, and the tokens."""
    from repro.models import lm
    from repro.models.registry import Arch
    from repro.serve import ServingEngine

    arch = Arch(cfg=dataclasses.replace(mla_moe_lm.program_config(cfg), dtype=dtype),
                module=lm)
    eng = ServingEngine(arch, params, max_seq=S + STEPS)
    prompt = np.random.default_rng(0).integers(0, s["vocab"], (B, S), dtype=np.int32)
    logits, cache = eng._prefill(params, {"tokens": jnp.asarray(prompt)})
    got, toks = [np.asarray(logits)[:, 0]], []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(STEPS - 1):
        toks.append(np.asarray(tok)[:, 0])
        logits, cache = eng._decode(params, tok, cache)
        got.append(np.asarray(logits)[:, 0])
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return np.stack(got, 1), np.concatenate([prompt, np.stack(toks, 1)], 1)


def test_program_config_is_the_published_one():
    cfg = bench_tiny.manifest.load_json(bench_tiny.BENCH / "configs" / "moonlight-16b-a3b.json")
    mc = mla_moe_lm.program_config(cfg)
    assert (mc.n_layers, mc.d_model, mc.n_heads, mc.d_ff, mc.d_ff_dense, mc.vocab) == (
        27, 2048, 16, 1408, 11264, 163840)
    assert (mc.kv_lora_rank, mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim) == (
        512, 128, 64, 128)
    assert (mc.n_experts, mc.experts_held, mc.expert_first, mc.top_k) == (64, 16, 0, 6)
    assert (mc.n_shared_experts, mc.first_dense_layers, mc.routed_scale) == (2, 1, 2.446)
    assert mc.router_score == "sigmoid" and mc.norm_eps == 1e-5
    assert mc.rope_theta == 50000.0 and mc.dtype == "bfloat16"


def test_reference_agrees_with_prefill_and_latent_decode(model):
    """At float32 the program routes as the reference does and its logits
    agree to float32 rounding (sums in another order, the absorbed decode);
    the tolerance, 1e-4 of the largest logit, is far below what bfloat16
    activations give (2**-8 relative a rounding), and the same program with
    bfloat16 activations fails it."""
    cfg, s, params = model
    got, seqs = _served_logits(cfg, s, params, "float32")
    ref, _ = mla_moe_lm.reference_forward(params, s, seqs, np.arange(S - 1, S - 1 + STEPS),
                                          block=1)
    scale = np.abs(ref).max()
    tol = 1e-4 * scale
    assert np.abs(got - ref).max() <= tol
    low, _ = _served_logits(cfg, s, params, "bfloat16")
    assert np.abs(low - ref).max() > tol


def test_reference_sees_bias_norms_rope_and_the_share(model):
    """Each of these changes the reference's logits: none is silently unused."""
    cfg, s, params = model
    toks = np.random.default_rng(1).integers(0, s["vocab"], (2, 10), dtype=np.int32)
    pos = np.arange(10)
    base, _ = mla_moe_lm.reference_forward(params, s, toks, pos)
    for path in (("layers", "moe", "router_bias"), ("layers", "attn", "kv_norm", "scale"),
                 ("dense_layers", "ln1", "scale"), ("layers", "moe", "shared", "wo"),
                 ("ln_f", "scale")):
        p2 = jax.tree.map(lambda a: a, params)
        node = p2
        for k in path[:-1]:
            node = node[k]
        node[path[-1]] = jnp.zeros_like(node[path[-1]])
        got, _ = mla_moe_lm.reference_forward(p2, s, toks, pos)
        assert np.abs(got - base).max() > 1e-3, path
    for change in ({"rope_theta": 10_000.0}, {"expert_first": 0}, {"routed_scale": 1.0}):
        s2 = dict(s, **change)
        got, _ = mla_moe_lm.reference_forward(params, s2, toks, pos)
        assert np.abs(got - base).max() > 1e-3


def test_route_margin_masks_the_positions_near_a_flip(model):
    """A position is masked when some MoE layer's K-th and (K+1)-th choice
    scores, one of them a held expert's, lie closer than ``route_margin``;
    the widest gap is read over the others only, and the logits are the
    reference's either way."""
    cfg, s, params = model
    toks = np.random.default_rng(2).integers(0, s["vocab"], (2, 12), dtype=np.int32)
    pos = np.arange(12)
    logits, margin = mla_moe_lm.reference_forward(params, s, toks, pos)
    assert margin.shape == (2, 12) and (margin >= 0).all() and np.isfinite(margin).any()
    assert not np.ma.getmaskarray(mla_moe_lm.reference_logits(params, s, toks, pos)).any()
    delta = float(np.median(margin))
    ref = mla_moe_lm.reference_logits(params, dict(s, route_margin=delta), toks, pos)
    open_ = margin < delta
    assert open_.any() and (~open_).any()
    np.testing.assert_array_equal(np.ma.getdata(ref), logits)
    np.testing.assert_array_equal(np.ma.getmaskarray(ref), np.broadcast_to(
        open_[..., None], logits.shape))
    # the worst token at every masked position, the best at every other
    worst, best = logits.argmin(-1), logits.argmax(-1)
    assert mla_moe_lm.widest_gap(ref, np.where(open_, worst, best)) == 0.0
    chosen = np.where(open_, best, worst)
    want = (logits.max(-1) - logits.min(-1))[~open_].max()
    assert mla_moe_lm.widest_gap(ref, chosen) == pytest.approx(want)
    # the control's logits are plain: the reference's mask alone decides
    low = mla_moe_lm.reference_logits(params, dict(s, route_margin=delta), toks, pos,
                                      control=True)
    assert not np.ma.isMaskedArray(low)
    # a plain array masks nothing, and a mask over every position counts all
    full = (logits.max(-1) - logits.min(-1)).max()
    assert mla_moe_lm.widest_gap(logits, worst) == pytest.approx(full)
    every = np.ma.masked_array(logits, mask=True)
    assert mla_moe_lm.widest_gap(every, worst) == pytest.approx(full)


def test_held_margin_by_hand():
    """K = 2 of 5 experts, experts 1 and 2 held: the margin is the distance
    of the held expert nearest the boundary between the 2nd and 3rd best."""
    s = {"top_k": 2, "expert_first": 1, "experts_held": 2}
    # scores 0.9, 0.7 | 0.6, 0.5, 0.1: held 0.7 (chosen) is 0.1 above the
    # 3rd; held 0.6 (not chosen) is 0.1 below the 2nd
    c = np.array([[0.9, 0.7, 0.6, 0.5, 0.1],
                  # held 0.95 and 0.2: the chosen one 0.95 - 0.5 above the
                  # 3rd, the other 0.8 - 0.2 below the 2nd
                  [0.8, 0.95, 0.2, 0.5, 0.1],
                  # both held below the 2nd (0.8): the nearer 0.8 - 0.7
                  [0.9, 0.3, 0.7, 0.8, 0.1]])
    np.testing.assert_allclose(mla_moe_lm.held_margin(c, s), [0.1, 0.45, 0.1], atol=1e-7)
    # the boundary of other experts alone does not count: 0.5 and 0.45 swap
    # freely, the held 0.95 (0.5 above the 3rd) and 0.1 (0.4 below the 2nd)
    # stay put
    c = np.array([[0.5, 0.95, 0.1, 0.45, 0.05]])
    np.testing.assert_allclose(mla_moe_lm.held_margin(c, s), [0.4], atol=1e-7)


def test_tiny_run_is_correct_and_records_routing():
    from repro.serve import ROUTING

    n0 = len(ROUTING.rounds)
    result, rec = bench_tiny_moe.run_tiny(seed=2**31 + 11, seconds=1.0)
    assert result["correct"] and result["failed"] == 0 and rec.rounds
    assert result["checks"]["token_gap"]["value"] <= 0.1
    # one record per round: the warm-up's and the window's
    assert len(ROUTING.rounds) - n0 == len(rec.rounds) + 1
