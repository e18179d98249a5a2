"""The MoE serving cell's work counts and readers, by hand and on a recorded
slice of a traced run on a TPU v5e."""

import types

import bench_tiny
import numpy as np
import pytest

from benchlib import mla_moe_lm, moe_readers, moe_work
from benchlib import trace as T
from benchlib.harness import Profiler, Round, RunRecord
from benchlib.peaks import Peaks, peaks_for

TINY = {"d_model": 4, "n_heads": 2, "kv_lora_rank": 3, "qk_nope": 2, "qk_rope": 2,
        "v_head_dim": 2, "d_ff": 2, "d_ff_dense": 8, "n_shared": 1, "n_experts": 4,
        "experts_held": 2, "expert_first": 0, "top_k": 2, "n_layers": 3,
        "n_dense_layers": 1, "vocab": 10, "dtype_bytes": 2}


def reader(name):
    return bench_tiny.manifest.metric_reader(name)


def test_moe_prefill_flops_by_hand():
    # per layer: q 4*2*4, latent 4*5, up-projection 3*2*4, o 2*2*4 = 92
    # weights; the dense layer's MLP 3*4*8, each MoE layer's shared expert
    # 3*4*2 and router 4*4; per token 2 * (3*92 + 96 + 2*(24 + 16)) = 904
    # causal attention over S = 5: scores (4) and values (2) of 2 heads, 15
    # pairs; the head at the last position 2*4*10; each routed pair 2*3*4*2
    want = 7 * (5 * 904 + 3 * (2 * 2 * 6 * 15) + 80) + 48 * 11
    assert moe_work.moe_prefill_flops(TINY, batch=7, seq=5, routed_pairs=11) == want


def test_moonlight_cell_work_scale():
    cfg = bench_tiny.manifest.load_json(bench_tiny.BENCH / "configs" / "moonlight-16b-a3b.json")
    s = mla_moe_lm.sizes(cfg)
    # 16 prompts of 1024 tokens, 1.5 of the 6 choices a token landing on the
    # 16 held experts in each of 26 MoE layers: about 2.6 GFLOP a token
    f = moe_work.moe_prefill_flops(s, 16, 1024, 16 * 1024 * 1.5 * 26)
    assert 4.1e13 < f < 4.4e13


def _record(entries):
    return types.SimpleNamespace(rounds=list(entries))


def _counts(prefill, decode):
    return np.asarray(prefill, np.int32), np.asarray(decode, np.int32)


def _run(t_end=10.0):
    """Two served rounds of 2 prompts of 5 tokens, 3 tokens each, and the
    routing record: a warm-up round's entry before them, one for each."""
    rounds = [Round(send=1.0, readbacks=[1.2, 1.3, 1.4], served=2, requested=2),
              Round(send=2.0, readbacks=[2.2, 2.3, 2.4], served=2, requested=2)]
    rec = RunRecord(t0=0.5, t_end=t_end, model=TINY, peaks=Peaks(1e3, 1e3, "test"))
    rec.rounds = rounds
    rec.launches = [(rd.send, rd.served, 5) for rd in rounds]
    # prefill: 2 MoE layers x (2 held + elsewhere), 2 rows x 5 tokens x 2
    # choices = 20 pairs a layer; decode: 2 steps read back, 4 pairs a layer
    pre = [[6, 2, 12], [4, 4, 12]]
    dec = [[[1, 0, 3], [2, 1, 1]], [[0, 0, 4], [1, 1, 2]]]
    entries = [(0.4, *_counts(pre, dec)), (1.45, *_counts(pre, dec)),
               (2.45, *_counts([[5, 5, 10], [2, 2, 16]], dec))]
    return rec, _record(entries)


def test_routing_joins_each_round_by_time():
    rec, record = _run()
    got = moe_readers.routing_by_round(rec, record)
    assert [g[0][0, 0] for g in got] == [6, 5]  # the warm-up's entry is not joined
    assert got[0][1].shape == (2, 2, 3)
    record.rounds.pop()  # the second round left no entry
    assert moe_readers.routing_by_round(rec, record)[1] is None


def test_expert_imbalance_by_hand():
    rec, record = _run()
    # round 1: layers 6/4 and 4/4; round 2: 5/5 and 2/2
    assert moe_readers.expert_imbalance(rec, record) == pytest.approx((1.5 + 1 + 1 + 1) / 4)
    assert moe_readers.expert_imbalance(rec, _record([])) is None


def _traced(rec, t0, t1, core_s, decode_s, n_decode):
    """A trace of one fused launch and ``n_decode`` decode steps."""
    ns = 1e9
    events = [T.Event("/host:CPU", "python3", T.WINDOW_SPAN, t0 * ns, (t1 - t0) * ns),
              T.Event("/device:TPU:0", T.MODULES_LINE, "jit_core(1)", t0 * ns, core_s * ns)]
    at = t0 + core_s
    for _ in range(n_decode):
        events.append(T.Event("/device:TPU:0", T.MODULES_LINE, "jit_decode_step(2)",
                              at * ns, decode_s * ns))
        at += decode_s
    rec.profiler = Profiler()
    rec.profiler.t0, rec.profiler.t1 = t0, t1
    rec.profiler.summary = T.reduce_events(events)
    return rec


def test_prefill_mfu_and_decode_roofline_by_hand():
    """The prefill share by hand (the decode roofline's reader is gone: no
    cell reports the token rate it moves)."""
    rec, record = _run()
    rec = _traced(rec, 1.9, 2.5, 0.05, 0.01, 3)  # the second round
    flops = moe_work.moe_prefill_flops(TINY, 2, 5, 14)
    assert moe_readers.prefill_mfu_pct(rec, record) == pytest.approx(
        100.0 * flops / (0.05 * 1e3))
    assert moe_readers.prefill_mfu_pct(rec, _record([])) is None


def test_counters_of_padded_rows_are_scaled_to_the_served():
    # 2 rows counted, 1 served: half of the held pairs are the served row's
    counts = np.asarray([[6, 2, 12], [4, 4, 12]])
    assert moe_readers.prefill_pairs(TINY, counts, served=1, seq=5) == 8.0


def recorded_moe_run():
    """A slice of a traced run of the moonlight cell recorded on a TPU v5e:
    the traced window's first fused launch and the decode step after it,
    with the run's record of the round before it, its own and the next, and
    the program's routing record of those rounds."""
    doc = bench_tiny.manifest.load_json(bench_tiny.BENCH / "tests" / "data" / "moe_trace.json")
    rec = RunRecord(t0=doc["t0"], t_end=doc["t_end"], model=doc["model"],
                    peaks=peaks_for(doc["device_kind"]))
    rec.rounds = [Round(*r) for r in doc["rounds"]]
    rec.launches = [tuple(x) for x in doc["launches"]]
    rec.profiler = Profiler()
    rec.profiler.t0, rec.profiler.t1 = doc["profiler"]
    rec.profiler.summary = T.reduce_events([T.Event(*row) for row in doc["events"]])
    record = _record([(t, np.asarray(p, np.int32), np.asarray(d, np.int32))
                      for t, p, d in doc["routing"]])
    return rec, record


@pytest.fixture
def recorded(monkeypatch):
    """The recorded run, with its routing record as the program's own."""
    import repro.serve.engine

    rec, record = recorded_moe_run()
    monkeypatch.setattr(repro.serve.engine, "ROUTING", record)
    return rec


@pytest.mark.parametrize("name", ["prefill_mfu_pct"])
def test_recorded_moe_trace_shares_lie_within_their_peak(recorded, name):
    assert 0 < getattr(moe_readers, name)(recorded) <= 100


def test_recorded_moe_trace_prefill_mfu_metric(recorded):
    assert reader("prefill_mfu.moe")(recorded) == moe_readers.prefill_mfu_pct(recorded)


def test_recorded_moe_trace_counters(recorded):
    s = recorded.model
    tr = recorded.trace
    assert tr.module_n["jit_core"] == 1 and tr.module_n["jit_decode_step"] == 1
    routing = moe_readers.routing_by_round(recorded)
    assert all(r is not None for r in routing)
    n_moe = s["n_layers"] - s["n_dense_layers"]
    for (pre, dec), rd in zip(routing, recorded.rounds):
        # every (token, choice) pair of every MoE layer is counted once
        assert pre.shape == (n_moe, s["experts_held"] + 1)
        assert (pre.sum(axis=1) == rd.served * 1024 * s["top_k"]).all()
        assert dec.shape == (len(rd.readbacks) - 1, n_moe, s["experts_held"] + 1)
        assert (dec.sum(axis=2) == rd.served * s["top_k"]).all()
    assert 1 < reader("expert_imbalance.moe")(recorded) < s["experts_held"]
