"""The reduction from a device trace to busy time, op times and idle gaps."""

import bench_tiny
import pytest

from benchlib import readers
from benchlib import trace as T
from benchlib.harness import RunRecord

DEV, HOST = "/device:TPU:0", "/host:CPU"


def ev(plane, line, name, start, dur):
    return T.Event(plane, line, name, float(start), float(dur))


def synthetic():
    """A 1000 ns window: two device ops (one overlapping the window's start),
    one module event, host spans over the gaps."""
    return [
        ev(HOST, "python", "bench.window", 100, 1000),
        ev(HOST, "python", "serve.fetch", 100, 300),
        ev(HOST, "python", "serve.round", 100, 1000),
        ev(HOST, "python", "PjitFunction(core)", 400, 10),  # not a benchmark span
        ev(DEV, "XLA Ops", "fusion.1", 50, 100),  # clipped to [100, 150)
        ev(DEV, "XLA Ops", "%fn.1 = u8[1,8,128]{2,1,0} custom-call(u8[1,64,48] %copy), "
                           'custom_call_target="tpu_custom_call"', 500, 200),
        ev(DEV, "XLA Ops", "fusion.2", 600, 200),  # overlaps fn.1
        ev(DEV, "XLA Modules", "jit_core(42)", 500, 300),
        ev(DEV, "XLA Ops", "late", 2000, 10),  # after the window
    ]


def test_busy_ops_modules_and_gaps():
    s = T.reduce_events(synthetic())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((50 + 300) * 1e-9)  # [100,150) and [500,800)
    assert s.idle_share == pytest.approx(0.65)
    assert s.op_s == pytest.approx({"fusion.1": 50e-9, "fn.1 custom-call u8[1,8,128]": 200e-9,
                                    "fusion.2": 200e-9})
    assert s.module_s == pytest.approx({"jit_core": 300e-9}) and s.module_n == {"jit_core": 1}
    assert s.op_seconds(readers.CODEC_KERNEL) == (pytest.approx(200e-9), 1)
    # gap [150, 500): fetch covers 250 of it, the round 350 -> the round; the
    # gap [800, 1100) lies under the round only
    assert s.idle_by_span == pytest.approx({"serve.round": 650e-9})
    b = s.breakdown()
    assert b["device_ops"][0][1] == pytest.approx(200e-9) and len(b["idle_gaps"]) == 1


def test_gap_named_by_most_overlapping_span():
    events = synthetic()
    events[2] = ev(HOST, "python", "serve.round", 900, 100)
    s = T.reduce_events(events)
    # [150, 500) -> serve.fetch (overlap 250); [800, 1100) -> serve.round (100)
    assert s.idle_by_span == pytest.approx({"serve.fetch": 350e-9, "serve.round": 300e-9})


def test_window_is_required():
    with pytest.raises(ValueError):
        T.reduce_events([e for e in synthetic() if e.name != "bench.window"])


def test_recorded_trace(tmp_path):
    """A slice of a trace recorded on a TPU v5e by a read-mix run."""
    events = T.events_from_json(bench_tiny.BENCH / "tests" / "data" / "read_trace.json")
    s = T.reduce_events(events)
    assert 0 < s.busy_s < s.window_s and s.devices == 1
    kernel_s, n = s.op_seconds(readers.CODEC_KERNEL)
    assert n > 0 and 0 < kernel_s <= s.busy_s
    # the JSON form round-trips
    T.events_to_json(events, tmp_path / "t.json")
    assert T.events_from_json(tmp_path / "t.json") == events


def test_readers_leave_out_what_they_cannot_read():
    rec = RunRecord()
    for name in ("codec_roofline.read", "device_idle.read", "fused_step_mfu",
                 "decode_step_ms", "ttft_p50_ms", "output_tokens_per_s", "read_p99_ms",
                 "decode_step_roofline", "inter_token_ms.serve", "mfu.serve"):
        assert bench_tiny.manifest.metric_reader(name)(rec) is None, name
