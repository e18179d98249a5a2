"""The readers of what the program records itself: the proxy's per-request
phase times and task counts, and its thread-busy counter."""

import bench_tiny
import pytest

from benchlib import manifest, program_readers
from benchlib.harness import Request, RunRecord
from repro.storage.proxy import RequestResult, Telemetry

READERS = ["admit_wait_ms.read", "service_ms.read", "decode_wait_ms.read",
           "useful_tasks.read", "thread_busy.read"]


def _result(op, arrival, injected, first_start, kth, done, ok, k, n_issued, started):
    return RequestResult(key="obj", op=op, n=n_issued, k=k, ok=ok, data=None,
                         t_arrival=arrival, t_first_start=first_start, t_done=done,
                         t_injected=injected, t_kth=kth, n_issued=n_issued,
                         tasks_started=started)


def _record() -> tuple[RunRecord, Telemetry]:
    """Two answered reads, one failed read and one write of proxy 7, one read
    of another run's proxy, and the benchmark's view of the first four, by
    hand (seconds)."""
    tel = Telemetry()
    results = [
        _result("read", 100.001, 100.011, 100.012, 100.112, 100.122, True, 6, 12, 10),
        _result("read", 101.001, 101.031, 101.034, 101.334, 101.364, True, 2, 2, 2),
        # failed: none of its numbers count
        _result("read", 102.0, 102.5, 102.6, None, 103.0, False, 6, 12, 12),
        _result("write", 103.0, 104.0, 104.0, 105.0, 105.0, True, 6, 12, 12),
    ]
    # another proxy's read, not in this window
    tel.results.append((3, _result("read", 50.0, 51.0, 51.0, 52.0, 53.0, True, 1, 9, 9)))
    tel.results.extend((7, res) for res in results)
    # 16 threads, from 100 s to the last send at 104 s: 12.8 thread-seconds
    # busy of 64; proxy 3's samples do not count
    tel.busy.extend([(7, 90.0, 0.0, 16), (3, 99.0, 1.0, 4), (7, 99.5, 3.2, 16),
                     (7, 102.0, 9.0, 16), (3, 103.0, 5.0, 4), (7, 104.0, 16.0, 16),
                     (7, 106.0, 30.0, 16)])
    run = RunRecord(t0=100.0, t_end=110.0)
    run.requests = [Request(res.op, due=res.t_arrival - 0.001, send=res.t_arrival,
                            first_start=res.t_first_start, done=res.t_done, ok=res.ok)
                    for res in results]
    run.requests[-1].send = 104.0
    return run, tel


@pytest.mark.parametrize("name,want", [
    ("admit_wait_ms.read", (10.0 + 30.0) / 2),
    ("service_ms.read", (100.0 + 300.0) / 2),
    ("decode_wait_ms.read", (10.0 + 30.0) / 2),
    ("useful_tasks.read", 100.0 * (6 + 2) / (10 + 2)),
    ("thread_busy.read", 100.0 * 12.8 / 64.0),
])
def test_reader_on_a_hand_built_record(name, want, monkeypatch):
    run, tel = _record()
    monkeypatch.setattr(program_readers, "telemetry", lambda: tel)
    assert manifest.metric_reader(name)(run) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_records_nothing(name, monkeypatch):
    # What a program without the record leaves: the benchmark's own times only.
    run, _ = _record()
    monkeypatch.setattr(program_readers, "telemetry", lambda: None)
    assert manifest.metric_reader(name)(run) is None
    # and a record that holds none of this run's requests
    monkeypatch.setattr(program_readers, "telemetry", lambda: Telemetry())
    assert manifest.metric_reader(name)(run) is None


def test_readers_report_on_a_tiny_read_run():
    result, rec = bench_tiny.run_tiny(bench_tiny.READ, seed=2**31 + 77, trace=True)
    assert result["correct"]
    got = {n: result["metrics"][n]["value"] for n in READERS}
    assert all(v > 0 for v in got.values()), got
    assert got["useful_tasks.read"] <= 100.0 and got["thread_busy.read"] <= 100.0
    # every answered read of the window is found in the program's record ...
    found = [res for _, res in program_readers.results(rec, "read")]
    assert len(found) == sum(1 for r in rec.requests if r.ok) > 0
    # ... and its phases tile its time from arrival to answer
    total = sum(res.t_done - res.t_arrival for res in found) / len(found) * 1e3
    queued = sum(res.t_first_start - res.t_injected for res in found) / len(found) * 1e3
    parts = (got["admit_wait_ms.read"] + queued + got["service_ms.read"]
             + got["decode_wait_ms.read"])
    assert parts == pytest.approx(total, rel=1e-6)
