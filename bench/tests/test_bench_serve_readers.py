"""The serving cell's readers on hand-built runs."""

import math

import bench_tiny
import pytest

from benchlib import readers, work
from benchlib import trace as T
from benchlib.harness import Profiler, Round, RunRecord
from benchlib.peaks import Peaks, peaks_for

TINY = {"d_model": 4, "n_heads": 2, "n_kv_heads": 1, "head_dim": 2, "d_ff": 8,
        "n_layers": 3, "vocab": 10, "dtype_bytes": 2}


def reader(name):
    return bench_tiny.manifest.metric_reader(name)


def serve_run(rounds, t_end, seq=5):
    rec = RunRecord(t0=0.0, t_end=t_end, model=TINY, peaks=Peaks(1e3, 1e3, "test"))
    rec.rounds = rounds
    rec.launches = [(rd.send, rd.served, seq) for rd in rounds]
    return rec


def test_ttft_counts_each_round_once():
    rec = serve_run([Round(send=0.0, readbacks=[0.1, 0.2], served=4, requested=4),
                     Round(send=1.0, readbacks=[1.3, 1.4], served=4, requested=4),
                     Round(send=2.0, readbacks=[2.2, 2.3], served=4, requested=4)], 9.0)
    # three rounds of four requests: three samples, 100, 300 and 200 ms
    assert readers.ttft_round_ms(rec) == pytest.approx([100.0, 300.0, 200.0])
    assert reader("ttft_p50_ms")(rec) == pytest.approx(200.0)


def test_ttft_counts_an_unserved_request_as_missing():
    rec = serve_run([Round(send=0.0, readbacks=[0.1], served=4, requested=4),
                     Round(send=1.0, readbacks=[1.2], served=3, requested=4),
                     Round(send=2.0, readbacks=[], served=0, requested=4)], 9.0)
    assert readers.ttft_round_ms(rec)[0] == pytest.approx(100.0)
    assert all(math.isinf(v) for v in readers.ttft_round_ms(rec)[1:])
    assert math.isinf(reader("ttft_p50_ms")(rec))


def test_inter_token_gap_by_hand():
    rec = serve_run([Round(send=0.0, readbacks=[0.10, 0.13, 0.17], served=4, requested=4),
                     Round(send=1.0, readbacks=[1.20, 1.22, 1.25], served=4, requested=4)],
                    1.24)
    # gaps 30, 40 and 20 ms close inside the window; 30 ms (1.22 -> 1.25) does not
    assert reader("inter_token_ms.serve")(rec) == pytest.approx(30.0)
    assert reader("inter_token_ms.serve")(serve_run([], 1.0)) is None


def test_serve_mfu_counts_the_work_read_back_in_the_window():
    rec = serve_run([Round(send=0.0, readbacks=[0.1, 0.2, 0.3], served=2, requested=2),
                     Round(send=0.5, readbacks=[0.6, 0.7, 1.2], served=2, requested=2)], 1.0)
    prefill = work.dense_prefill_flops(TINY, 2, 5)
    # token j of a round comes from a decode step attending 5 + j positions
    step1 = work.dense_decode_step_work(TINY, [6, 6])[0]
    step2 = work.dense_decode_step_work(TINY, [7, 7])[0]
    want = 2 * prefill + 2 * step1 + step2  # the last token comes after the close
    assert reader("mfu.serve")(rec) == pytest.approx(100.0 * want / (1.0 * 1e3))


def test_serve_readers_need_one_launch_per_round():
    rec = serve_run([Round(send=0.0, readbacks=[0.1, 0.2], served=2, requested=2)], 1.0)
    rec.launches = []
    with pytest.raises(ValueError):
        reader("mfu.serve")(rec)



def recorded_serve_run():
    """A slice of a traced serve run recorded on a TPU v5e: the traced
    window's first fused launch and the decode step after it, with the run's
    record of the round before it, its own and the next."""
    doc = bench_tiny.manifest.load_json(bench_tiny.BENCH / "tests" / "data" / "serve_trace.json")
    rec = serve_run([Round(*r) for r in doc["rounds"]], doc["t_end"])
    rec.t0, rec.model = doc["t0"], doc["model"]
    rec.launches = [tuple(x) for x in doc["launches"]]
    rec.peaks = peaks_for(doc["device_kind"])
    rec.profiler = Profiler()
    rec.profiler.t0, rec.profiler.t1 = doc["profiler"]
    rec.profiler.summary = T.reduce_events([T.Event(*row) for row in doc["events"]])
    return rec


@pytest.mark.parametrize("name", ["fused_step_mfu", "decode_step_roofline"])
def test_recorded_serve_trace_shares_lie_within_their_peak(name):
    assert 0 < reader(name)(recorded_serve_run()) <= 100


@pytest.mark.parametrize("name", ["decode_step_ms", "device_idle.serve"])
def test_recorded_serve_trace_is_read(name):
    rec = recorded_serve_run()
    tr = rec.trace
    assert tr.module_n[readers.FUSED_MODULE] == 1 and tr.module_n[readers.DECODE_MODULE] == 1
    want = {"decode_step_ms": 1e3 * tr.module_s[readers.DECODE_MODULE],
            "device_idle.serve": 100.0 * (1 - tr.busy_s / tr.window_s)}[name]
    assert 0 < reader(name)(rec) == pytest.approx(want)


def test_recorded_serve_inter_token_gap_by_hand():
    rec = recorded_serve_run()
    gaps = []
    for rd in rec.rounds:
        for a, b in zip(rd.readbacks, rd.readbacks[1:]):
            if b < rec.t_end:
                gaps.append(b - a)
    assert len(gaps) >= 15
    assert reader("inter_token_ms.serve")(rec) == pytest.approx(1e3 * sum(gaps) / len(gaps))
