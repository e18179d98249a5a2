"""Proxy batched read path: admission-round decode, raw reads, and the
paper's heavy-load adaptation (backlog pressure → fewer/larger chunks)."""

import threading
import time

import numpy as np
import pytest

from repro.coding.layout import SharedKeyLayout
from repro.core import (
    PAPER_READ_3MB,
    FeedbackPolicy,
    RequestClass,
    StaticPolicy,
    TOFECPolicy,
)
from repro.storage import (
    FaultyStore,
    MemoryStore,
    Proxy,
    StorageError,
    store_coded_object,
)

LAYOUT = SharedKeyLayout(K=6, r=2, strip_bytes=128)


class _GatedStore(MemoryStore):
    """Deterministic fake store: ranged reads block until the gate opens,
    with a controllable post-gate delay. Lets a test pile up a backlog of
    known size before ANY task completes."""

    def __init__(self):
        super().__init__()
        self.gate = threading.Event()
        self.range_calls = 0
        self._count_lock = threading.Lock()

    def get_range(self, key, offset, length):
        self.gate.wait()
        with self._count_lock:
            self.range_calls += 1
        return super().get_range(key, offset, length)


def _payloads(rng, count, nbytes):
    return [rng.integers(0, 256, size=nbytes, dtype=np.uint8).tobytes() for _ in range(count)]


def test_read_many_batch_decodes_heterogeneous_erasures():
    """One round of concurrent reads with random per-item failures: every
    item reconstructs despite each surviving a different erasure pattern
    (the admission round's single batched decode path)."""
    rng = np.random.default_rng(0)
    inner = MemoryStore()
    payloads = _payloads(rng, 8, LAYOUT.file_bytes - 11)
    keys = []
    for i, p in enumerate(payloads):
        store_coded_object(inner, f"obj/{i}", LAYOUT, p)
        keys.append(f"obj/{i}")
    store = FaultyStore(inner, p_fail=0.15, seed=1)
    proxy = Proxy(store, StaticPolicy(12, 6), L=8)
    try:
        results = proxy.read_many(keys, LAYOUT, payload_len=len(payloads[0]))
        assert all(r.ok for r in results)
        for r, p in zip(results, payloads):
            assert r.data == p
    finally:
        proxy.close()


def test_raw_read_returns_chunks_for_external_decode():
    """raw=True skips proxy decode; the chunks round-trip through the
    layout's own reconstruct (what the fused serving step does in-jit)."""
    rng = np.random.default_rng(2)
    store = MemoryStore()
    payload = _payloads(rng, 1, LAYOUT.file_bytes)[0]
    store_coded_object(store, "raw/0", LAYOUT, payload)
    proxy = Proxy(store, StaticPolicy(6, 3), L=4)
    try:
        res = proxy.read("raw/0", LAYOUT, payload_len=len(payload), raw=True)
        assert res.ok and res.data is None
        assert res.chunks is not None and len(res.chunks) >= res.k
        got = LAYOUT.reconstruct(res.k, res.chunks, payload_len=len(payload))
        assert got == payload
    finally:
        proxy.close()


def test_mixed_chunk_levels_share_one_admission_round():
    """Reads admitted at different k levels all reconstruct correctly via
    the per-item present masks of the shared (N, K) strip code."""
    rng = np.random.default_rng(3)
    inner = MemoryStore()
    payloads = _payloads(rng, 6, LAYOUT.file_bytes)
    keys = []
    for i, p in enumerate(payloads):
        store_coded_object(inner, f"mix/{i}", LAYOUT, p)
        keys.append(f"mix/{i}")

    class _CyclePolicy(StaticPolicy):
        """Cycles the chunk level so one round mixes k = 6, 3, 2, 1."""

        def __init__(self):
            super().__init__(12, 6)
            self._cycle = [(12, 6), (6, 3), (4, 2), (2, 1), (3, 3), (2, 2)]
            self._i = 0

        def select(self, *, q, idle, cls_id=0, now=None):
            out = self._cycle[self._i % len(self._cycle)]
            self._i += 1
            return out

    proxy = Proxy(inner, _CyclePolicy(), L=8)
    try:
        results = proxy.read_many(keys, LAYOUT, payload_len=LAYOUT.file_bytes)
        assert all(r.ok for r in results)
        assert sorted({r.k for r in results}) == [1, 2, 3, 6]
        for r, p in zip(results, payloads):
            assert r.data == p
    finally:
        proxy.close()


def test_backlog_pressure_shifts_code_toward_fewer_chunks():
    """The paper's heavy-load behavior on the real-I/O proxy: as the gated
    backlog builds, TOFEC picks fewer/larger chunks (k drops from k_max
    toward 1), deterministically — selection happens at submission time
    while the store blocks every task."""
    rng = np.random.default_rng(4)
    store = _GatedStore()
    count = 24
    payloads = _payloads(rng, count, LAYOUT.file_bytes)
    keys = []
    for i, p in enumerate(payloads):
        store_coded_object(store, f"load/{i}", LAYOUT, p)
        keys.append(f"load/{i}")

    cls = RequestClass("gated", LAYOUT.file_bytes / 2**20, PAPER_READ_3MB,
                       k_max=6, r_max=2.0, n_max=12)
    proxy = Proxy(store, TOFECPolicy.for_classes([cls], L=8), L=8)
    try:
        # Submit the whole backlog while the store admits nothing.
        reqs = [proxy.read_async(k, LAYOUT, payload_len=LAYOUT.file_bytes) for k in keys]
        store.gate.set()
        results = [proxy.wait(r, timeout=60.0) for r in reqs]
        assert all(r.ok for r in results)
        for r, p in zip(results, payloads):
            assert r.data == p
        ks = [r.k for r in results]
        assert ks[0] == 6  # empty queue → max chunking (light-load optimum)
        assert ks[-1] == 1  # deep backlog → no chunking (heavy-load optimum)
        # Monotone non-increasing in submission order: the EWMA only grows
        # while the gate is closed (modulo the one-in-flight admission slot).
        assert all(b <= a + 1 for a, b in zip(ks, ks[1:]))
        assert {1, 6} <= set(ks)
    finally:
        proxy.close()


class _OffsetFailStore(MemoryStore):
    """Fails ranged reads for one key past a byte offset — a deterministic
    'this object lost most of its strips' fault."""

    def __init__(self, bad_key, max_offset):
        super().__init__()
        self.bad_key = bad_key
        self.max_offset = max_offset

    def get_range(self, key, offset, length):
        if key == self.bad_key and offset >= self.max_offset:
            raise StorageError(f"simulated loss: {key}@{offset}")
        return super().get_range(key, offset, length)


def test_raw_batch_surfaces_per_item_error_mask():
    """A partially-failed item in a raw batch reports ok=False with its
    surviving chunks, while the rest of the batch completes normally —
    per-item error mask, not an all-or-nothing batch failure."""
    rng = np.random.default_rng(7)
    payloads = _payloads(rng, 4, LAYOUT.file_bytes)
    # chunks 0-3 of the k=6 level survive; 4-11 are gone → < k readable
    store = _OffsetFailStore("part/1", 4 * LAYOUT.strip_bytes)
    keys = []
    for i, p in enumerate(payloads):
        store_coded_object(store, f"part/{i}", LAYOUT, p)
        keys.append(f"part/{i}")
    proxy = Proxy(store, StaticPolicy(12, 6), L=8)
    try:
        results = proxy.read_many(keys, LAYOUT, payload_len=LAYOUT.file_bytes,
                                  raw=True)
        assert [r.ok for r in results] == [True, False, True, True]
        bad = results[1]
        assert bad.chunks is not None and 0 < len(bad.chunks) < bad.k
        for ci, blob in bad.chunks.items():  # what arrived is still intact
            off, ln = LAYOUT.chunk_range(bad.k, ci)
            assert blob == payloads[1][0:0] + store.get("part/1")[off:off + ln]
        for r, p in zip(results, payloads):
            if r.ok:
                got = LAYOUT.reconstruct(r.k, r.chunks, payload_len=len(p))
                assert got == p
    finally:
        proxy.close()


def test_closed_write_path_recodes_after_midrun_switch():
    """Tentpole round-trip: the controller's fed-back (n, k) governs how the
    NEXT queued write is encoded, while objects written under the old code
    stay readable. Exercises write → flush → registry-guided read."""
    rng = np.random.default_rng(8)
    store = MemoryStore()
    wp = FeedbackPolicy(12, 6)
    proxy = Proxy(store, StaticPolicy(12, 6), L=8, write_policy=wp)
    pa = _payloads(rng, 1, LAYOUT.file_bytes)[0]
    pb = _payloads(rng, 1, LAYOUT.file_bytes)[0]
    try:
        ra = proxy.write("w/a", LAYOUT, pa)
        assert ra.ok and (ra.n, ra.k) == (12, 6)
        wp.push(2, 2)  # controller adapts: heavy load → fewer, larger chunks
        rb = proxy.write("w/b", LAYOUT, pb)
        assert rb.ok and (rb.n, rb.k) == (2, 2)
        proxy.flush_writes()
        # the two stored objects really are different codes of the shared
        # strip space: full (12,6) codeword vs the 2-chunk (k=2, m=3) prefix
        assert len(store.get("w/a")) == 12 * LAYOUT.strip_bytes
        assert len(store.get("w/b")) == 2 * 3 * LAYOUT.strip_bytes
        for key, p in [("w/a", pa), ("w/b", pb)]:
            res = proxy.read(key, LAYOUT, payload_len=len(p))
            assert res.ok and res.data == p
    finally:
        proxy.close()


# ---------------------------------------------------------------------------
# Phase timestamps, task counts and the thread-busy counter
# ---------------------------------------------------------------------------


def _latency_proxy(L=4, seed=0):
    """A proxy over the paper's task delays at 2 ms of wall time per emulated s."""
    from repro.storage import LatencyStore

    inner = MemoryStore()
    store = LatencyStore(inner, PAPER_READ_3MB, time_scale=2e-3, seed=seed)
    cls = RequestClass("latency", LAYOUT.file_bytes / 2**20, PAPER_READ_3MB,
                       k_max=6, r_max=2.0, n_max=12)
    return inner, Proxy(store, TOFECPolicy.for_classes([cls], L=L), L=L)


@pytest.mark.parametrize("op", ["read", "write"])
def test_answered_requests_keep_phase_order_and_task_counts(op):
    rng = np.random.default_rng(21)
    inner, proxy = _latency_proxy()
    payloads = _payloads(rng, 12, LAYOUT.file_bytes)
    try:
        if op == "read":
            for i, p in enumerate(payloads):
                store_coded_object(inner, f"o/{i}", LAYOUT, p)
            reqs = [proxy.read_async(f"o/{i}", LAYOUT, LAYOUT.file_bytes)
                    for i in range(len(payloads))]
        else:
            reqs = [proxy.write_async(f"o/{i}", LAYOUT, p)
                    for i, p in enumerate(payloads)]
        results = [proxy.wait(r, 30.0) for r in reqs]
        proxy.flush_writes(timeout=30.0)
    finally:
        proxy.close()
    assert all(r.ok for r in results)
    if op == "read":
        assert [r.data for r in results] == payloads
    assert sorted(r.rid for r in results) == list(range(len(payloads)))
    for r in results:
        assert r.t_arrival <= r.t_injected <= r.t_first_start <= r.t_kth <= r.t_done
        assert r.k <= r.tasks_started <= r.n_issued <= r.n


def test_thread_busy_counts_store_time_within_its_bounds():
    L = 4
    rng = np.random.default_rng(22)
    inner, proxy = _latency_proxy(L=L, seed=1)
    payloads = _payloads(rng, 16, LAYOUT.file_bytes)
    for i, p in enumerate(payloads):
        store_coded_object(inner, f"o/{i}", LAYOUT, p)
    t0 = time.monotonic()
    try:
        assert proxy.thread_busy_s == 0.0
        results = proxy.read_many([f"o/{i}" for i in range(len(payloads))], LAYOUT,
                                  LAYOUT.file_bytes, timeout=30.0)
        # a cancelled task still running finishes within its emulated delay
        time.sleep(0.2)
        elapsed = time.monotonic() - t0
        busy = proxy.thread_busy_s
    finally:
        proxy.close()
    assert all(r.ok for r in results)
    # every task sleeps its emulated delay inside the store call
    started = sum(r.tasks_started for r in results)
    assert started * 2e-3 * PAPER_READ_3MB.delta_bar / 8 < busy <= L * elapsed


def test_profiler_trace_tags_a_reads_spans_with_its_rid(tmp_path):
    from profiler_events import named, profiled

    rng = np.random.default_rng(23)
    inner, proxy = _latency_proxy()
    payloads = _payloads(rng, 6, LAYOUT.file_bytes)
    for i, p in enumerate(payloads):
        store_coded_object(inner, f"o/{i}", LAYOUT, p)
    try:
        with profiled(tmp_path) as events:
            handles = [proxy.read_async(f"o/{i}", LAYOUT, LAYOUT.file_bytes)
                       for i in range(len(payloads))]
            results = [proxy.wait(h, 30.0) for h in handles]
            # a cancelled read's running tasks end within their emulated delay
            time.sleep(0.2)
    finally:
        proxy.close()
    assert all(r.ok for r in results)
    waits = {ev[3]["rid"]: ev for ev in named(events, "proxy.admit_wait")}
    tasks = named(events, "proxy.task")
    assert named(events, "proxy.admit_round") and named(events, "proxy.reconstruct")
    for h, r in zip(handles, results):
        mine = [ev for ev in tasks if ev[3]["rid"] == r.rid]
        # one task span per task a worker ran, each after the read's admission
        # wait; the answer counts those started by then
        assert len(mine) == h.tasks_started >= r.tasks_started >= r.k
        assert all(ev[1] >= waits[r.rid][2] for ev in mine)
        # distinct chunks of the read's chunk-level code
        chunks = [ev[3]["chunk"] for ev in mine]
        assert len(set(chunks)) == len(chunks)
        assert max(chunks) < LAYOUT.code_for_k(r.k)[0]


def test_telemetry_keeps_answers_without_payloads_and_busy_samples():
    from repro.storage.proxy import TELEMETRY

    rng = np.random.default_rng(24)
    inner, proxy = _latency_proxy(L=4, seed=2)
    payloads = _payloads(rng, 8, LAYOUT.file_bytes)
    for i, p in enumerate(payloads):
        store_coded_object(inner, f"o/{i}", LAYOUT, p)
    try:
        results = proxy.read_many([f"o/{i}" for i in range(len(payloads))], LAYOUT,
                                  LAYOUT.file_bytes, timeout=30.0)
        # a cancelled task still running finishes within its emulated delay
        time.sleep(0.2)
        busy = proxy.thread_busy_s
    finally:
        proxy.close()
    assert [r.data for r in results] == payloads
    mine = {res.t_done: res for s, res in list(TELEMETRY.results) if s == proxy.serial}
    assert sorted(mine) == sorted(r.t_done for r in results)
    for r in results:
        kept = mine[r.t_done]
        assert kept.data is None and kept.chunks is None
        assert (kept.rid, kept.t_injected, kept.t_kth, kept.tasks_started) == \
            (r.rid, r.t_injected, r.t_kth, r.tasks_started)
    samples = [(t, b, n) for s, t, b, n in list(TELEMETRY.busy) if s == proxy.serial]
    assert samples[0][1] == 0.0 and {n for _, _, n in samples} == {4}
    assert all(a[0] <= b[0] and a[1] <= b[1] for a, b in zip(samples, samples[1:]))
    assert samples[-1][1] == busy
