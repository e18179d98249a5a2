"""Arithmetic for the readers of what the program records itself: the
phase times and task counts the proxy keeps on each answered request
(``RequestResult``), and its ``thread_busy_s`` counter.

The program keeps both for every proxy of the process in
``repro.storage.proxy.TELEMETRY``, which a reader takes after the run. A
window's answered request is joined to the program's result by its answer
time: the driver copies ``RequestResult.t_done`` into ``Request.done``. A
program without that record, or without a quantity on it, gives None.
"""

from __future__ import annotations

import bisect

from benchlib import stats


def telemetry():
    """The program's process-wide proxy record, or None where it has none."""
    try:
        from repro.storage.proxy import TELEMETRY
    except ImportError:
        return None
    return TELEMETRY


def results(run, op: str, tel=None) -> list[tuple]:
    """(proxy serial, RequestResult) of each answered request of ``op`` in
    the window that the program's record holds."""
    tel = tel if tel is not None else telemetry()
    if tel is None:
        return []
    by_done = {res.t_done: (serial, res) for serial, res in list(tel.results)}
    hits = (by_done.get(r.done) for r in run.requests
            if r.op == op and r.ok and r.done is not None)
    return [h for h in hits if h is not None]


def phase_mean_ms(run, op: str, start: str, end: str, tel=None):
    """Mean of ``end - start`` over the answered requests of ``op``, two of
    the ``RequestResult`` fields ``t_arrival``, ``t_injected``,
    ``t_first_start``, ``t_kth`` and ``t_done``."""
    d = []
    for _, res in results(run, op, tel):
        a, b = getattr(res, start, None), getattr(res, end, None)
        if a is not None and b is not None:
            d.append((b - a) * 1e3)
    return stats.mean(d) if d else None


def useful_tasks_pct(run, op: str, tel=None):
    """The k chunks a request uses as a share of the tasks it started."""
    hits = [res for _, res in results(run, op, tel)]
    started = sum(getattr(res, "tasks_started", 0) for res in hits)
    return 100.0 * sum(res.k for res in hits) / started if started else None


def _busy_at(samples: list[tuple], t: float):
    """The counter's value at host time ``t``: its last sample at or before."""
    i = bisect.bisect_right([s[0] for s in samples], t)
    return samples[i - 1][1] if i else None


def thread_busy_pct(run, op: str, tel=None):
    """Thread-seconds in store calls over the threads' seconds, from the
    window's start to the generator's last send, over the proxies that
    answered the window's requests of ``op``."""
    tel = tel if tel is not None else telemetry()
    serials = {serial for serial, _ in results(run, op, tel)}
    sends = [r.send for r in run.requests]
    if not serials or not sends or max(sends) <= run.t0:
        return None
    t0, t1 = run.t0, max(sends)
    busy = threads = 0.0
    for serial in serials:
        samples = [(t, b, L) for s, t, b, L in list(tel.busy) if s == serial]
        b0, b1 = _busy_at(samples, t0), _busy_at(samples, t1)
        if b0 is None or b1 is None:
            return None
        busy += b1 - b0
        threads += samples[0][2]
    return 100.0 * busy / (threads * (t1 - t0))
