"""Work counts of the benchmark against hand counts."""

import bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import dense_lm, work


def test_codec_item_work_by_hand():
    # decode of a 3 MiB object at k = 6: 6 rows of 512 KiB in and out,
    # a 48 x 48 bit-matrix times 48 bit-planes of 524288 columns
    ops, byts = work.codec_item_work(6, 6, 524288)
    assert ops == 2 * 48 * 48 * 524288
    assert byts == 12 * 524288
    # encode of 6 parity strips from 6 data strips, 1 KiB each
    assert work.codec_item_work(6, 6, 1024) == (2 * 48 * 48 * 1024, 12 * 1024)


def test_codec_call_least_time_takes_the_larger_bound():
    items = [(6, 6, 524288)] * 2
    ops, byts = 2 * 2.0 * 48 * 48 * 524288, 2 * 12.0 * 524288
    assert work.codec_call_least_s(items, 197e12, 819e9) == pytest.approx(
        max(ops / 197e12, byts / 819e9))
    # a slow link makes the bytes the bound
    assert work.codec_call_least_s(items, 197e12, 1e3) == pytest.approx(byts / 1e3)


def test_dense_prefill_flops_by_hand():
    cfg = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2, "d_ff": 8,
           "n_layers": 3, "vocab": 10}
    # per token per layer: q 2*4*4, k and v 2*2*4*2, o 2*4*4, MLP 3*2*4*8
    per_token = 32 + 32 + 32 + 192
    # causal attention over S = 5: scores and values, 2 heads of 2, 15 pairs
    attn = 2 * (2 * 2 * 2) * 15
    want = 7 * (5 * 3 * per_token + 3 * attn + 2 * 4 * 10)
    assert work.dense_prefill_flops(cfg, batch=7, seq=5) == want


def test_qwen_prefill_flops_scale():
    cfg = {"hidden_size": 1024, "num_attention_heads": 16, "num_key_value_heads": 16,
           "intermediate_size": 2816, "num_hidden_layers": 24, "vocab_size": 151936,
           "rope_theta": 1e6, "rms_norm_eps": 1e-6, "initializer_range": 0.02,
           "torch_dtype": "bfloat16"}
    s = dense_lm.sizes(cfg)
    # 16 prompts of 1024 tokens: 16384 tokens x 617 MFLOP of weights, plus
    # attention and 16 last-position heads
    f = work.dense_prefill_flops(s, 16, 1024)
    assert 1.08e13 < f < 1.10e13


def test_dense_decode_step_work_by_hand():
    cfg = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2, "d_ff": 8,
           "n_layers": 3, "vocab": 10, "dtype_bytes": 2}
    # matmul weights per layer: q 4*4, k and v 4*2 each, o 4*4, MLP 3*4*8;
    # the tied head 10*4, counted once
    layer_mm, head = 16 + 8 + 8 + 16 + 96, 40
    # two sequences attending 5 and 7 positions: 12 positions in all
    ops = 2 * (3 * layer_mm + head) * 2 + 3 * (2 * 2 * 2 * 2) * 12
    # weights with the q, k, v biases (4 + 2 + 2) and two norms of 4 per
    # layer, and the final norm; keys and values (1 head of 2) of the 12
    # attended positions read and of the 2 new ones written
    weights = 3 * (layer_mm + 8 + 8) + head + 4
    kv = 3 * 2 * 2 * (12 + 2)
    assert work.dense_decode_step_work(cfg, [5, 7]) == (ops, (weights + kv) * 2)


def test_least_time_names_its_bound():
    assert work.least_s(10.0, 1.0, 10.0, 10.0) == (1.0, "flops")
    assert work.least_s(1.0, 10.0, 10.0, 10.0) == (1.0, "bytes")


def test_qwen_decode_step_is_bound_by_bytes():
    cfg = {"hidden_size": 1024, "num_attention_heads": 16, "num_key_value_heads": 16,
           "intermediate_size": 2816, "num_hidden_layers": 24, "vocab_size": 151936,
           "rope_theta": 1e6, "rms_norm_eps": 1e-6, "initializer_range": 0.02,
           "torch_dtype": "bfloat16"}
    s = dense_lm.sizes(cfg)
    # 16 sequences, each attending its 1024-token prompt and the new token:
    # 0.93 GB of weights and 1.61 GB of keys and values
    ops, byts = work.dense_decode_step_work(s, [1025] * 16)
    assert 2.50e9 < byts < 2.58e9 and 1.6e10 < ops < 1.7e10
    t, bound = work.least_s(ops, byts, 197e12, 819e9)
    assert bound == "bytes" and t == pytest.approx(byts / 819e9)


def test_reference_code_matches_the_stored_format():
    """The benchmark's own Reed-Solomon encoder gives the program's strips."""
    import numpy as np

    from benchlib import rs_ref
    from repro.coding import rs

    assert rs_ref.mul(0x80, 2) == 0x1D and rs_ref.inv(1) == 1
    assert all(rs_ref.mul(a, rs_ref.inv(a)) == 1 for a in range(1, 256))
    data = np.random.default_rng(0).integers(0, 256, (6, 64), dtype=np.uint8)
    for n, k in ((12, 6), (7, 6), (6, 6)):
        np.testing.assert_array_equal(rs_ref.encode(data, n, k), rs.encode(data, n, k))
