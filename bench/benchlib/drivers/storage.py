"""Driver for object-store deployments: an open loop of reads or writes sent
through the TOFEC proxy (``repro.storage.proxy.Proxy``) to an emulated S3.

Set-up makes a pool of objects from the seed on the device, pre-codes them
in one batched encode where the mix reads them, and warms every codec
bucket the mix can hit. The window then sends each request at its due time
(``read_async`` / ``write_async``), whatever the proxy's backlog, and every
latency runs from the due time to the answer (a write's answer is its
acknowledgement at k durable parts).

The check, after the window: every read's payload equals the stored one;
for writes, every key's last acknowledged write reads back through the proxy
after ``flush_writes``, and a sample of the stored coded objects equals the
plain reference code's strips (``rs_ref``) byte for byte. Every request due in the window has to
answer, a minute past the close at most.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchlib import rs_ref, stats
from benchlib.harness import Check, Phases, Request, RunRecord, Span, memory_peak_bytes

MIB = 2**20
#: the longest a request due in the window may take to answer
ANSWER_TIMEOUT_S = 60.0
#: stored objects of a write mix compared strip by strip with the reference
ORACLE_SAMPLE = 16
#: how often the write window hands settled writes back (flush_writes)
FLUSH_EVERY_S = 0.5


class RecordingCodec:
    """The program's codec, passed to the proxy unchanged, with every call's
    unpadded shape recorded as (t0, t1, kind, [(m, k, B), ...])."""

    def __init__(self, inner, log: list):
        self.inner, self.log = inner, log

    def encode(self, data, n, k, *, n_out=None):
        t0 = time.monotonic()
        out = self.inner.encode(data, n, k, n_out=n_out)
        batch, _, B = np.shape(data) if np.ndim(data) == 3 else (1, *np.shape(data))
        m = (n if n_out is None else n_out) - k
        if m > 0:
            self.log.append((t0, time.monotonic(), "enc", [(m, k, B)] * batch))
        return out

    def decode(self, rows, present, n, k):
        t0 = time.monotonic()
        out = self.inner.decode(rows, present, n, k)
        batch, _, B = np.shape(rows) if np.ndim(rows) == 3 else (1, *np.shape(rows))
        self.log.append((t0, time.monotonic(), "dec", [(k, k, B)] * batch))
        return out

    def __getattr__(self, name):
        return getattr(self.inner, name)


def spanned_store(inner):
    """The emulated S3 with each task's call wrapped in a benchmark span."""
    from repro.storage.backend import ObjectStore

    class SpannedStore(ObjectStore):
        def put(self, key, data):
            with Span("s3.put"):
                inner.put(key, data)

        def get(self, key):
            with Span("s3.get"):
                return inner.get(key)

        def get_range(self, key, offset, length):
            with Span("s3.get_range"):
                return inner.get_range(key, offset, length)

        def upload_part(self, key, part_id, data):
            with Span("s3.upload_part"):
                inner.upload_part(key, part_id, data)

        def complete_multipart(self, key, part_ids):
            inner.complete_multipart(key, part_ids)

        def delete(self, key):
            inner.delete(key)

        def exists(self, key):
            return inner.exists(key)

        def keys(self):
            return inner.keys()

    return SpannedStore()


def _pool(seed_key: int, count: int, K: int, b: int):
    """``count`` random payloads of K strips of b bytes, made on the device."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda key: jax.random.bits(key, (count, K, b), jnp.uint8))(
        jax.random.key(seed_key))


def _warm(codec, layout, op: str, max_batch: int) -> None:
    """One call per codec bucket the mix can hit: decode for reads, encode for
    writes, at every power-of-two batch up to ``max_batch``. (An encode's
    bucket depends on the full code's parity count, not on the adapted one.)"""
    K, N, b = layout.K, layout.N, layout.strip_bytes
    batch = 1
    while batch <= max_batch:
        zeros = np.zeros((batch, K, b), np.uint8)
        if op == "read":
            codec.decode(zeros, np.tile(np.arange(K), (batch, 1)), N, K)
        else:
            codec.encode(zeros, N, K)
        batch *= 2


def run(ctx) -> RunRecord:
    import jax

    from repro.coding.codec import get_codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core.controller import TOFECPolicy
    from repro.core.delay_model import DelayParams, RequestClass
    from repro.storage.backend import LatencyStore, MemoryStore, StorageError
    from repro.storage.proxy import Proxy

    phases = Phases(ctx.t_proc0)
    phases.mark("start")
    cfg, tr = ctx.config, ctx.traffic
    op = tr["op"]
    if op not in ("read", "write"):
        raise ValueError(f"traffic op {op!r}: want read or write")
    rec = RunRecord()
    rng = np.random.default_rng(ctx.seed)
    layout = SharedKeyLayout(**cfg["layout"])
    if layout.file_bytes != cfg["object_bytes"]:
        raise ValueError("layout does not hold object_bytes")
    read_p = DelayParams(**cfg["read_delay"])
    write_p = DelayParams(**cfg["write_delay"])
    objects = int(cfg["objects"])

    codec = ctx.codec or get_codec()
    rcodec = RecordingCodec(codec, rec.codec_calls)
    inner = MemoryStore()
    store = spanned_store(
        LatencyStore(inner, read_p, write_p, time_scale=float(cfg["time_scale"]),
                     seed=int(rng.integers(2**62))))
    cls = RequestClass(cfg["name"], layout.file_bytes / MIB,
                       read_p if op == "read" else write_p,
                       k_max=layout.K, r_max=float(layout.r), n_max=layout.N)
    proxy = Proxy(store, TOFECPolicy.for_classes([cls], L=int(cfg["L"])),
                  L=int(cfg["L"]), codec=rcodec)
    try:
        # -- set-up: objects from the seed, pre-coded in one batched encode --
        pool_dev = _pool(int(rng.integers(2**31)), objects, layout.K, layout.strip_bytes)
        if op == "read":
            coded = np.asarray(codec.encode(pool_dev, layout.N, layout.K))
            phases.mark("pre-code")
            for i in range(objects):
                inner.put(f"obj/{i}", coded[i].tobytes())
            del coded
        pool = np.asarray(pool_dev).reshape(objects, layout.file_bytes)
        del pool_dev
        pool_bytes = [pool[i].tobytes() for i in range(objects)] if op == "write" else None
        phases.mark("objects")
        _warm(codec, layout, op, int(tr["max_codec_batch"]))
        jax.effects_barrier()
        phases.mark("warm-up")
        phases.print()

        dues = stats.poisson_arrivals(rng, float(tr["rate_per_s"]), ctx.seconds)
        key_draws = rng.integers(objects, size=len(dues))
        pool_draws = rng.integers(objects, size=len(dues))
        compiles0 = ctx.compiles.count if ctx.compiles else 0
        if ctx.trace:
            from benchlib.harness import Profiler

            rec.profiler = Profiler()
            rec.profiler.start()

        # -- the window --------------------------------------------------------
        rec.t0 = time.monotonic()
        rec.setup_s = rec.t0 - ctx.t_proc0
        rec.t_end = rec.t0 + ctx.seconds
        stop = threading.Event()
        flusher = None
        if op == "write":
            def flush_loop():
                while not stop.wait(FLUSH_EVERY_S):
                    proxy.flush_writes(timeout=ANSWER_TIMEOUT_S)

            flusher = threading.Thread(target=flush_loop, name="bench-flush", daemon=True)
            flusher.start()
        handles = []
        last_write: dict[int, tuple] = {}  # key index -> (request handle, pool index)
        for i, due in enumerate(dues):
            t_due = rec.t0 + float(due)
            wait = t_due - time.monotonic()
            if wait > 0:
                with Span("gen.sleep"):
                    time.sleep(wait)
            if op == "read":
                ki = int(key_draws[i])
                with Span("gen.submit"):
                    t_send = time.monotonic()
                    h = proxy.read_async(f"obj/{ki}", layout, layout.file_bytes)
                handles.append((Request("read", t_due, t_send, key_index=ki), h))
            else:
                # the drawn key, or the next one whose last write has settled
                ki = int(key_draws[i])
                for step in range(objects):
                    prev = last_write.get((ki + step) % objects)
                    if prev is None or prev[0].settled.is_set():
                        ki = (ki + step) % objects
                        break
                pj = int(pool_draws[i])
                with Span("gen.submit"):
                    t_send = time.monotonic()
                    h = proxy.write_async(f"obj/{ki}", layout, pool_bytes[pj])
                last_write[ki] = (h, pj)
                handles.append((Request("write", t_due, t_send, key_index=ki, answer=pj), h))
        for req, h in handles:
            h.done.wait(max(rec.t_end + ANSWER_TIMEOUT_S - time.monotonic(), 0.0))
            res = h.result
            if res is not None:
                req.first_start, req.done, req.ok = res.t_first_start, res.t_done, res.ok
                if op == "read":
                    req.answer = res.data
            rec.requests.append(req)
        if rec.profiler is not None:
            rec.profiler.stop()
        stop.set()
        if flusher is not None:
            flusher.join(timeout=ANSWER_TIMEOUT_S)
        rec.compiles_in_window = (ctx.compiles.count if ctx.compiles else 0) - compiles0
        rec.memory_peak_bytes = memory_peak_bytes(ctx.devices or jax.devices()[:1])

        # -- the check ------------------------------------------------------------
        rec.attempted = len(rec.requests)
        rec.failed = sum(1 for r in rec.requests if not r.ok)
        unanswered = sum(1 for r in rec.requests if r.done is None)
        rec.checks.append(Check("unanswered", unanswered, 0))
        if op == "read":
            wrong = 0
            for r in rec.requests:
                if r.ok and not np.array_equal(np.frombuffer(r.answer, np.uint8),
                                               pool[r.key_index]):
                    wrong += 1
                r.answer = None
            rec.checks.append(Check("wrong_payloads", wrong, 0))
        else:
            proxy.flush_writes(timeout=ANSWER_TIMEOUT_S)
            final = {ki: pj for ki, (_, pj) in last_write.items()}
            keys = sorted(final)
            backs = proxy.read_many([f"obj/{ki}" for ki in keys], layout, layout.file_bytes,
                                    timeout=ANSWER_TIMEOUT_S)
            unreadable = sum(1 for ki, b in zip(keys, backs)
                             if not b.ok or b.data != pool_bytes[final[ki]])
            rec.checks.append(Check("unreadable_after_flush", unreadable, 0))
            wrong = 0
            for ki in rng.choice(keys, size=min(ORACLE_SAMPLE, len(keys)), replace=False):
                try:
                    obj = np.frombuffer(inner.get(f"obj/{ki}"), np.uint8)
                except StorageError:  # acknowledged, never stored
                    wrong += 1
                    continue
                obj = obj.reshape(-1, layout.strip_bytes)
                want = rs_ref.encode(pool[final[ki]].reshape(layout.K, layout.strip_bytes),
                                     layout.N, layout.K)
                if not np.array_equal(obj, want[: obj.shape[0]]):
                    wrong += 1
            rec.checks.append(Check("stored_strips_wrong", wrong, 0))
    finally:
        proxy.close()
    return rec
