"""Unified batched MDS codec engine — one API over numpy / jnp / Pallas.

TOFEC's proxy re-picks the (n, k) MDS code on *every* arrival (§IV-C), so
the coding hot path sees a stream of heterogeneous codes. A naive jit-per-
(n, k) design retraces on each code change and encodes object-by-object;
this engine instead exposes one batched API

    encode(data: (batch, k, B)) -> (batch, n, B)      # systematic
    decode(rows: (batch, k, B), present)  -> (batch, k, B)

with **shape-bucketed jit caching**: compiled kernels are keyed on
(kind, k, bucket(n - k), bucket(B), bucket(batch)) and the actual GF(256)
coding matrices travel as *traced array inputs* (tiny, built host-side from
the cached Cauchy generator), so any (n, k) stream from ``TOFECPolicy``
reuses a small set of compilations instead of retracing per code. ``decode``
accepts a per-item ``present`` matrix, so one batched call reconstructs many
objects that each survived a *different* erasure pattern.

Backends (registry-selected):

* ``numpy``  — the table oracle (vectorized log/exp gathers on host). No
  compilation; the reference all others are tested against.
* ``jnp``    — pure ``jax.numpy`` log/exp-table backend (gather + XOR fold),
  vmap-free batched formulation, jit-cached per bucket.
* ``pallas`` — the GF(2) bit-matrix MXU kernel
  (:func:`repro.kernels.gf2mm.gf2mm.gf2_rs_matmul_bytes`), batched over the
  grid with bitplane pack/unpack fused into the kernel.

Selection: ``get_codec("jnp")`` explicitly, or ``get_codec()``, which takes
``REPRO_CODEC_BACKEND`` when it is set and otherwise follows the device:
``pallas`` on a TPU, the ``numpy`` oracle elsewhere. The Pallas backend runs
the interpreter on the CPU only (:func:`repro.kernels.gf2mm.gf2mm.
resolve_interpret`); on a TPU it always runs the compiled kernel.

Consumers: :mod:`repro.coding.layout` (file encode/reconstruct),
:mod:`repro.storage.proxy` (batched write-queue encode per admission round),
:mod:`repro.ckpt.checkpoint` (leaf sharding), and the codec throughput sweep
in ``benchmarks/kernel_bench.py``.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from repro import obs
from repro.coding import gf256, rs

__all__ = [
    "Codec",
    "CodecStats",
    "get_codec",
    "default_backend",
    "register_backend",
    "available_backends",
    "pow2_bucket",
]


def pow2_bucket(x: int, floor: int = 1) -> int:
    """Smallest power of two ≥ max(x, floor)."""
    b = max(floor, 1)
    while b < x:
        b <<= 1
    return b


def _is_traced(x) -> bool:
    """True when x is a JAX tracer (call made inside jit/vmap/grad)."""
    from jax.core import Tracer

    return isinstance(x, Tracer)


#: Back-compat alias — codec counters (calls/items/traces) now live on the
#: shared :class:`repro.obs.CompileStats` so retrace accounting is uniform.
CodecStats = obs.CompileStats


class _Backend:
    """One coding backend: batched GF(256) matmul + optional jit bucketing.

    The single primitive every backend implements is

        matmul(mats: (batch, m, k) GF(256), data: (batch, k, B) bytes)
            -> (batch, m, B) bytes

    — parity rows for encode, inverted-generator rows for decode. ``mats``
    is always a *runtime* array so code changes never retrace.
    """

    name = "base"
    jitted = False

    def __init__(self, stats: CodecStats):
        self.stats = stats
        self._fns: dict[tuple, object] = {}
        self._lock = threading.Lock()  # guards _fns mutation only

    def matmul(self, mats, data):  # pragma: no cover - interface
        raise NotImplementedError

    def prep_mats(self, mats):
        """Host-side prep of (already padded) coding matrices into the form
        :meth:`matmul_traced` consumes — identity for the table backends,
        GF(2) bit-expansion for pallas. Runs once per admission round on
        tiny arrays; the result is a valid runtime input to a jitted step."""
        return mats

    def matmul_traced(self, mats, data):
        """Trace-safe matmul for use INSIDE an outer ``jax.jit`` (the fused
        serving step): both operands may be tracers, ``mats`` having been
        through :meth:`prep_mats`. Host-only backends raise."""
        raise TypeError(
            f"codec backend {self.name!r} is host-only; use the jnp or "
            "pallas backend inside jit-traced code"
        )

    def _fn_for(self, key: tuple, build):
        """Shared-cache lookup; only the dict mutation is locked, so
        concurrent encodes on different (or same) buckets run in parallel."""
        with self._lock:
            fn = self._fns.get(key)
            if fn is None:
                with obs.span("codec.build", backend=self.name,
                              bucket=str(key)):
                    fn = self._fns[key] = build()
        return fn

    def to_host(self, arr) -> np.ndarray:
        return np.asarray(arr)


class NumpyBackend(_Backend):
    """Vectorized table oracle; no compilation, runs anywhere."""

    name = "numpy"

    def matmul(self, mats, data):
        mats = np.asarray(mats, np.uint8)
        data = np.asarray(data, np.uint8)
        batch, m, k = mats.shape
        B = data.shape[2]
        out = np.zeros((batch, m, B), np.uint8)
        for t in range(k):  # k ≤ 256 and static; avoids a (b, m, k, B) temp
            prod = gf256.mul(mats[:, :, t : t + 1], data[:, t : t + 1, :])
            np.bitwise_xor(out, prod, out=out)
        return out


class JnpBackend(_Backend):
    """Pure jax.numpy log/exp-table backend, jit-cached per shape bucket."""

    name = "jnp"
    jitted = True

    def _build(self, k: int):
        import jax
        import jax.numpy as jnp

        # Keep the tables as host numpy in the closure: _build may run while
        # an OUTER jit (the fused serving step) is tracing, and any device
        # array created here would be a tracer leaking into the cached fn.
        exp_np = gf256.exp_table()
        log_np = gf256.log_table()

        def fn(mats, data):
            self.stats.traces += 1  # runs at trace time only
            exp = jnp.asarray(exp_np, jnp.int32)
            log = jnp.asarray(log_np, jnp.int32)
            a = mats.astype(jnp.int32)  # (batch, m, k)
            d = data.astype(jnp.int32)  # (batch, k, B)
            la, ld = log[a], log[d]
            out = jnp.zeros((a.shape[0], a.shape[1], d.shape[2]), jnp.int32)
            for t in range(k):  # static fold over the contraction dim
                prod = exp[la[:, :, t, None] + ld[:, None, t, :]]
                prod = jnp.where(
                    (a[:, :, t, None] == 0) | (d[:, None, t, :] == 0), 0, prod
                )
                out = jnp.bitwise_xor(out, prod)
            return out.astype(jnp.uint8)

        return jax.jit(fn)

    def matmul(self, mats, data):
        import jax.numpy as jnp

        k = mats.shape[2]
        key = (k, mats.shape[0], mats.shape[1], data.shape[2])
        fn = self._fn_for(key, lambda: self._build(k))
        return fn(jnp.asarray(mats), jnp.asarray(data))

    # The log/exp-table formulation is already trace-safe: shapes come from
    # the (possibly traced) operands and the inner jit inlines under an
    # outer jit, so the fused serving step reuses the same kernel.
    matmul_traced = matmul


class PallasBackend(_Backend):
    """GF(2) bit-matrix MXU kernel; fused bytes→bitplanes→bytes path."""

    name = "pallas"
    jitted = True

    def __init__(self, stats: CodecStats, interpret: bool | None = None):
        from repro.kernels.gf2mm.gf2mm import resolve_interpret

        super().__init__(stats)
        self.interpret = resolve_interpret(interpret)

    def _build(self, k: int):
        import jax

        from repro.kernels.gf2mm.gf2mm import gf2_rs_matmul_bytes

        def fn(bitmats, data):
            self.stats.traces += 1  # runs at trace time only
            return gf2_rs_matmul_bytes(bitmats, data, interpret=self.interpret)

        return jax.jit(fn)

    def prep_mats(self, mats):
        """GF(2) bit-expansion (batch, m, k) → (batch, 8m, 8k); host-side."""
        return gf256.expand_bitmatrix_batched(np.asarray(mats, np.uint8))

    def matmul_traced(self, bitmats, data):
        """Kernel dispatch on pre-expanded bit-matrices; safe under jit."""
        import jax.numpy as jnp

        k = bitmats.shape[2] // 8
        key = (k, bitmats.shape[0], bitmats.shape[1] // 8, data.shape[2])
        fn = self._fn_for(key, lambda: self._build(k))
        return fn(jnp.asarray(bitmats), jnp.asarray(data))

    def matmul(self, mats, data):
        return self.matmul_traced(self.prep_mats(mats), data)


class Codec:
    """Batched systematic Cauchy-RS codec over a pluggable backend.

    All entry points accept and return host ``np.ndarray``; jitted backends
    move data through the device internally. Shape bucketing (powers of two
    on batch, parity count and strip width, zero-padded, sliced on exit)
    keeps the compiled-kernel set small under heterogeneous (n, k) streams.
    """

    #: floor for the strip-width bucket — keeps tile shapes lane-aligned.
    B_FLOOR = 128

    def __init__(self, backend: str | None = None, *, interpret: bool | None = None):
        name = backend or default_backend()
        if name not in _REGISTRY:
            raise ValueError(f"unknown codec backend {name!r}; have {sorted(_REGISTRY)}")
        self.stats = CodecStats(label=f"codec.{name}")
        if name == "pallas":
            self.backend: _Backend = _REGISTRY[name](self.stats, interpret=interpret)
        else:
            self.backend = _REGISTRY[name](self.stats)
        self.name = name

    # -- bucketing ----------------------------------------------------------

    def bucket_key(self, kind: str, n: int, k: int, B: int, batch: int) -> tuple:
        """The compilation-cache key a call with these params lands in."""
        if not self.backend.jitted:
            return (self.name,)
        m = k if kind == "dec" else n - k
        return (kind, k, pow2_bucket(m), pow2_bucket(B, self.B_FLOOR), pow2_bucket(batch))

    def _pad(self, arr, batch_b: int, B_b: int):
        batch, rows, B = arr.shape
        if batch == batch_b and B == B_b:
            return arr
        if isinstance(arr, np.ndarray):
            out = np.zeros((batch_b, rows, B_b), np.uint8)
            out[:batch, :, :B] = arr
            return out
        import jax.numpy as jnp  # traced / device input

        return jnp.zeros((batch_b, rows, B_b), jnp.uint8).at[:batch, :, :B].set(arr)

    def _as_bytes(self, arr):
        """(uint8 view, use_jnp flag) for the input.

        numpy inputs stay on host and come back as numpy. On the jitted
        backends, jax inputs — tracers (calls made under ``jax.jit``) and
        concrete device arrays alike — stay in jax-land end to end, so the
        codec composes with compiled steps and skips host round-trips.
        """
        if _is_traced(arr):
            if not self.backend.jitted:
                raise TypeError(
                    f"codec backend {self.name!r} is host-only; use the jnp or "
                    "pallas backend inside jit-traced code"
                )
            import jax.numpy as jnp

            return jnp.asarray(arr, jnp.uint8), True
        if self.backend.jitted:
            import jax

            if isinstance(arr, jax.Array):
                import jax.numpy as jnp

                return jnp.asarray(arr, jnp.uint8), True
        return np.asarray(arr, np.uint8), False

    # -- batched API --------------------------------------------------------

    def encode(self, data, n: int, k: int, *, n_out: int | None = None):
        """Systematic encode: (batch, k, B) → (batch, n, B). Also accepts a
        single codeword (k, B) and returns (n, B).

        ``n_out`` (k ≤ n_out ≤ n) produces only the FIRST n_out codeword rows
        — the write path's partial encode for an adapted (smaller) code.
        Cauchy parity rows depend on n − k, so this slices the full (n, k)
        parity matrix rather than building an (n_out, k) code: the emitted
        strips are bit-identical to a prefix of the full codeword and stay
        compatible with every chunking level of the same layout.

        numpy inputs return host numpy; on the jitted backends jax inputs
        (traced or concrete) return jax arrays, so the codec composes with
        compiled serving/checkpoint steps without host round-trips.
        """
        data, use_jnp = self._as_bytes(data)
        single = data.ndim == 2
        if single:
            data = data[None]
        if data.ndim != 3 or data.shape[1] != k:
            raise ValueError(f"data must be (batch, k={k}, B), got {data.shape}")
        if not 0 < k <= n:
            raise ValueError(f"need 0 < k <= n, got ({n=}, {k=})")
        if n_out is None:
            n_out = n
        elif not k <= n_out <= n:
            raise ValueError(f"need k <= n_out <= n, got ({n=}, {k=}, {n_out=})")
        batch, _, B = data.shape
        self.stats.calls += 1
        self.stats.items += batch
        if n_out == k:
            out = data
        else:
            # Prefix of the cached full parity matrix (see n_out docstring).
            par = rs.cauchy_parity_matrix(n, k)[: n_out - k]
            parity = self._matmul_bucketed("enc", par[None].repeat(batch, 0), data, n, k,
                                           use_jnp=use_jnp)
            if use_jnp:
                import jax.numpy as jnp

                out = jnp.concatenate([data, parity], axis=1)
            else:
                out = np.concatenate([data, parity], axis=1)
        return out[0] if single else out

    def decode(self, rows, present, n: int, k: int) -> np.ndarray:
        """Reconstruct data from any k surviving strips per item.

        rows: (batch, k, B) (or (k, B)); ``present`` is the strip ids of
        those rows — either one shared (k,) tuple or a per-item (batch, k)
        array, enabling one batched call across heterogeneous erasure
        patterns. Row order must match ``present`` (which must be concrete —
        it selects the host-side decode matrices — even when ``rows`` is
        traced).
        """
        rows, use_jnp = self._as_bytes(rows)
        single = rows.ndim == 2
        if single:
            rows = rows[None]
        if rows.ndim != 3 or rows.shape[1] != k:
            raise ValueError(f"rows must be (batch, k={k}, B), got {rows.shape}")
        batch, _, B = rows.shape
        present = np.asarray(present, np.int64)
        if present.ndim == 1:
            present = np.broadcast_to(present, (batch, k))
        if present.shape != (batch, k):
            raise ValueError(f"present must be (k,) or (batch, k), got {present.shape}")
        self.stats.calls += 1
        self.stats.items += batch
        out = self._matmul_bucketed("dec", self.decode_mats(present, n, k), rows, n, k,
                                    use_jnp=use_jnp)
        return out[0] if single else out

    def decode_mats(self, present, n: int, k: int) -> np.ndarray:
        """(batch, k, k) host decode matrices for per-item ``present``
        patterns — tiny inversions, cached per (n, k, pattern). This is the
        runtime-matrix input of the fused serving step: built host-side each
        round, fed to the jitted step as a traced array so erasure-pattern
        changes never retrace."""
        present = np.asarray(present, np.int64)
        if present.ndim == 1:
            present = present[None]
        return np.stack(
            [rs.decode_matrix(n, k, tuple(int(i) for i in p)) for p in present]
        )

    def pad_to_bucket(self, kind: str, mats: np.ndarray, data, n: int, k: int):
        """Zero-pad (mats, data) to the shape bucket this call lands in.

        Returns (mats_p, data_p, key) with key = :meth:`bucket_key`'s tuple.
        The ONE source of truth for bucket padding, shared by the unfused
        matmul path and the fused serving step (which feeds mats_p through
        ``backend.prep_mats`` into its own jitted launch); callers slice
        ``[:batch, :m, :B]`` off the result themselves."""
        batch, m, _ = mats.shape
        key = self.bucket_key(kind, n, k, data.shape[2], batch)
        if not self.backend.jitted:
            return mats, data, key
        _, _, m_b, B_b, batch_b = key
        mats_p = np.zeros((batch_b, m_b, k), np.uint8)
        mats_p[:batch, :m] = mats
        return mats_p, self._pad(data, batch_b, B_b), key

    def _matmul_bucketed(self, kind, mats, data, n, k, *, use_jnp=False):
        batch, m, _ = mats.shape
        B = data.shape[2]
        if not self.backend.jitted:
            return self.backend.matmul(mats, data)
        mats_p, data_p, _ = self.pad_to_bucket(kind, mats, data, n, k)
        out = self.backend.matmul(mats_p, data_p)
        if use_jnp:  # stay in jax-land (traced or device) for the caller
            return out[:batch, :m, :B]
        return self.backend.to_host(out)[:batch, :m, :B]

    # -- blob helpers (1-D payload convenience) -----------------------------

    @staticmethod
    def strip_bytes(payload_len: int, k: int) -> int:
        return -(-max(payload_len, 1) // k)

    def encode_blob(self, payload, *, n: int, k: int) -> np.ndarray:
        """1-D uint8 payload → (n, ceil(len/k)) coded strips."""
        return self.encode_blobs([payload], n=n, k=k)[0]

    def encode_blobs(self, payloads, *, n: int, k: int) -> list[np.ndarray]:
        """Batch-encode same-class payloads in ONE kernel launch.

        Payloads are packed to a common strip width (the max over the batch);
        each result is sliced back to its own ceil(len/k) strip width, which
        is lossless because coded columns depend only on same-index data
        columns (zero columns encode to zero).
        """
        bufs = [np.asarray(p, np.uint8).reshape(-1) for p in payloads]
        strips = [self.strip_bytes(b.size, k) for b in bufs]
        B = max(strips)
        data = np.zeros((len(bufs), k, B), np.uint8)
        for i, (b, s) in enumerate(zip(bufs, strips)):
            # Each blob keeps ITS OWN (k, strip_i) row layout, left-aligned
            # into the batch-max width; coded columns are column-independent,
            # so coded[i][:, :strip_i] equals the individually-encoded blob.
            row = np.zeros(k * s, np.uint8)
            row[: b.size] = b
            data[i, :, :s] = row.reshape(k, s)
        coded = self.encode(data, n, k)
        return [coded[i][:, : strips[i]] for i in range(len(bufs))]

    def decode_blob(self, strips, present, *, n: int, k: int, payload_len: int) -> np.ndarray:
        """Any k strips (k, strip) + their ids → payload bytes."""
        out = self.decode(np.asarray(strips, np.uint8), present, n, k)
        return out.reshape(-1)[:payload_len]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, type] = {}
_INSTANCES: dict[tuple, Codec] = {}
_INSTANCES_LOCK = threading.Lock()


def register_backend(name: str, cls: type) -> None:
    _REGISTRY[name] = cls


def available_backends() -> list[str]:
    return sorted(_REGISTRY)


def default_backend() -> str:
    """``REPRO_CODEC_BACKEND`` when set; otherwise the Pallas kernel on a TPU
    and the numpy oracle on any other backend."""
    env = os.environ.get("REPRO_CODEC_BACKEND")
    if env:
        return env
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "numpy"


def get_codec(backend: str | None = None, *, interpret: bool | None = None) -> Codec:
    """Process-wide codec instance per (backend, resolved interpret) pair.

    ``interpret`` only applies to the pallas backend; ``None`` resolves from
    the device at first use (interpreter on the CPU only), so explicit and
    defaulted callers share one instance (and its jit caches).
    """
    from repro.kernels.gf2mm.gf2mm import resolve_interpret

    name = backend or default_backend()
    interpret = resolve_interpret(interpret) if name == "pallas" else None
    key = (name, interpret)
    with _INSTANCES_LOCK:
        if key not in _INSTANCES:
            _INSTANCES[key] = Codec(name, interpret=interpret)
        return _INSTANCES[key]


register_backend("numpy", NumpyBackend)
register_backend("jnp", JnpBackend)
register_backend("pallas", PallasBackend)
