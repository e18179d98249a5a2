"""The readers of the proxy's decoder thread and of the backlog it hands its
policy: ``decode_batch_ms.read`` (``TELEMETRY.decodes``) and
``backlog_seen.read`` (``RequestResult.q``)."""

import dataclasses

import bench_tiny
import pytest

from benchlib import manifest, program_readers
from benchlib.harness import Request, RunRecord
from repro.storage.proxy import RequestResult, Telemetry

READERS = ["decode_batch_ms.read", "backlog_seen.read"]


def _result(arrival, done, q, ok=True, op="read"):
    return RequestResult(key="obj", op=op, n=2, k=1, ok=ok, data=None,
                         t_arrival=arrival, t_first_start=arrival + 0.1, t_done=done,
                         t_injected=arrival + 0.05, t_kth=done - 0.01, n_issued=2,
                         tasks_started=1, q=q)


def _record() -> tuple[RunRecord, Telemetry]:
    """Three answered reads and a failed one of proxy 7 in a window from 100
    s, one write, another proxy's read, and the decodes around them, by hand
    (seconds)."""
    tel = Telemetry()
    results = [
        _result(100.5, 100.9, q=0),
        _result(101.0, 101.4, q=3),
        _result(101.1, 101.5, q=5),
        _result(102.0, 102.5, q=9, ok=False),  # failed: not counted
        _result(102.1, 102.6, q=11, op="write"),  # a write: not counted
    ]
    tel.results.append((3, _result(50.0, 51.0, q=20)))  # another proxy
    tel.results.extend((7, res) for res in results)
    tel.decodes.extend([
        (7, 99.0, 99.5),  # before the window
        (7, 100.88, 100.89),  # 10 ms
        (3, 101.0, 101.2),  # another proxy
        (7, 101.37, 101.40),  # 30 ms
        (7, 101.6, 101.7),  # after the window's last answer
    ])
    run = RunRecord(t0=100.0, t_end=103.0)
    run.requests = [Request(res.op, due=res.t_arrival - 0.001, send=res.t_arrival,
                            first_start=res.t_first_start, done=res.t_done, ok=res.ok)
                    for res in results]
    return run, tel


@pytest.mark.parametrize("name,want", [
    ("decode_batch_ms.read", (10.0 + 30.0) / 2),
    ("backlog_seen.read", (0 + 3 + 5) / 3),
])
def test_reader_on_a_hand_built_record(name, want, monkeypatch):
    run, tel = _record()
    monkeypatch.setattr(program_readers, "telemetry", lambda: tel)
    assert manifest.metric_reader(name)(run) == pytest.approx(want, rel=1e-6)


@dataclasses.dataclass
class _OlderResult:
    """A program's answered request from before ``RequestResult.q``."""

    op: str
    k: int
    ok: bool
    t_done: float


class _OlderTelemetry:
    """A program's record from before ``Telemetry.decodes``."""

    def __init__(self, results):
        self.results = results
        self.busy = []


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_where_the_program_records_nothing(name, monkeypatch):
    run, tel = _record()
    monkeypatch.setattr(program_readers, "telemetry", lambda: None)
    assert manifest.metric_reader(name)(run) is None
    # a record that holds none of this run's requests
    monkeypatch.setattr(program_readers, "telemetry", lambda: Telemetry())
    assert manifest.metric_reader(name)(run) is None
    # a program without the deque and the field, as before the decoder thread
    older = _OlderTelemetry([(s, _OlderResult(r.op, r.k, r.ok, r.t_done))
                             for s, r in tel.results])
    monkeypatch.setattr(program_readers, "telemetry", lambda: older)
    assert manifest.metric_reader(name)(run) is None


def test_readers_report_on_a_tiny_read_run():
    from repro.storage.proxy import TELEMETRY

    result, rec = bench_tiny.run_tiny(bench_tiny.READ, seed=2**31 + 91, trace=True)
    assert result["correct"]
    got = {n: result["metrics"][n]["value"] for n in READERS}
    assert got["decode_batch_ms.read"] > 0 and got["backlog_seen.read"] >= 0, got
    found = program_readers.results(rec, "read")
    assert len(found) == sum(1 for r in rec.requests if r.ok) > 0
    # every answered read carries the backlog its policy was given ...
    assert all(isinstance(res.q, int) and res.q >= 0 for _, res in found)
    # ... and every read's decode by the run's proxy lies inside the run
    serial = found[0][0]
    mine = [d for d in TELEMETRY.decodes if d[0] == serial]
    t_last = max(res.t_done for _, res in found)
    assert mine and all(rec.t0 <= start <= end <= t_last for _, start, end in mine)
    assert len(mine) == len(found)
