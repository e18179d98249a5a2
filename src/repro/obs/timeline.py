"""Time-resolved telemetry: fixed-capacity on-device timelines.

:class:`TimelineBuf` is the windowed/ring twin of :class:`repro.obs.metrics.
MetricsBuf`: a registered-dataclass pytree of float32 per-slot series and
int32 per-slot histogram *deltas*, built from plain ``jnp`` ops so it
threads through ``jit`` / ``vmap`` / ``lax.scan`` without host syncs. Two
modes share one type:

* **windowed** (the sweep engines): :func:`sweep_timeline` folds a scan's
  (T,) per-request outputs into S = T/window slots — arrival rate, backlog,
  mean pick (n, k), served count, and a fixed-bucket delay histogram delta
  per window.  The window is ``timeline_window(T_bucket)``, derived from
  the pow2 time bucket so it rides the jit cache key without ever splitting
  a bucket (two runs sharing a time bucket share a window — and a trace).
* **ring**: :meth:`TimelineBuf.append` writes one slot per round at
  ``pos % capacity``, overwriting the oldest round once the
  ring wraps; :meth:`TimelineBuf.snapshot` restores oldest-first order.

Delay histograms use fixed log-spaced buckets (:data:`DELAY_BINS` bins,
:data:`DELAY_SUB` per octave from 2**:data:`DELAY_MIN_EXP` seconds, ~9%
width), so windowed percentiles are recoverable from the deltas at bucket
resolution (:func:`hist_percentile` / :func:`rolling_percentile`) — the
windowed-tail observable the SLO monitor (:mod:`repro.obs.slo`) consumes.

Chunk folds differ from MetricsBuf deliberately: timelines stay PER CASE,
so :meth:`reduce_rows` only cuts the tail padding and chunks concatenate
(:meth:`concat`) along the case axis instead of summing.  Per-case slots
are leading-batch invariant, which is what keeps streamed and mesh-sharded
timelines bit-exact against the materialized single-device path.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

#: Slot budget for sweep timelines: a pow2 time bucket T yields
#: T / timeline_window(T) <= TIMELINE_SLOTS windows.
TIMELINE_SLOTS = 64

#: Fixed log-spaced delay buckets: DELAY_SUB buckets per octave starting at
#: 2**DELAY_MIN_EXP seconds; the first/last buckets absorb the clipped
#: tails. 96 bins cover ~15.6 ms .. ~59 s at ~9% resolution.
DELAY_BINS = 96
DELAY_SUB = 8
DELAY_MIN_EXP = -6


def timeline_window(t_bucket: int) -> int:
    """Window size (arrivals per slot) for a pow2 time bucket.

    Derived deterministically from the bucket, so appending it to a sweep's
    jit cache key is explicit without ever creating a new compilation."""
    return max(int(t_bucket) // TIMELINE_SLOTS, 1)


def delay_bucket(value):
    """Traceable value -> bucket index under the fixed log-spaced buckets."""
    v = jnp.maximum(jnp.asarray(value, jnp.float32), 2.0 ** DELAY_MIN_EXP)
    idx = jnp.floor(jnp.log2(v) * DELAY_SUB).astype(jnp.int32)
    return jnp.clip(idx - DELAY_MIN_EXP * DELAY_SUB, 0, DELAY_BINS - 1)


def bucket_edges() -> np.ndarray:
    """(DELAY_BINS,) upper edges in seconds; bucket i spans (E[i-1], E[i]]."""
    i = np.arange(DELAY_BINS, dtype=np.float64)
    return 2.0 ** (DELAY_MIN_EXP + (i + 1) / DELAY_SUB)


def hist_percentile(hist, p: float) -> np.ndarray:
    """Recover a percentile from bucket counts (host side).

    ``hist``: (..., DELAY_BINS) counts.  Returns the upper edge of the
    bucket holding the p-quantile observation (<= ~9% conservative).  An
    all-zero row (a window that saw no observations) is explicitly NaN —
    never a clamped bucket edge — so downstream consumers
    (:func:`rolling_percentile` series, the SLO burn rate, the dashboards'
    gap-aware sparklines/polylines) can tell "no data" from "fast"."""
    h = np.asarray(hist, np.float64)
    tot = h.sum(axis=-1)
    cum = h.cumsum(axis=-1)
    target = p * tot
    idx = np.minimum((cum < target[..., None]).sum(axis=-1), DELAY_BINS - 1)
    out = bucket_edges()[idx]
    return np.where(tot > 0, out, np.nan)


def rolling_percentile(hist_rows, p: float, window: int) -> np.ndarray:
    """Percentile series over a trailing window of histogram delta rows.

    ``hist_rows``: (S, DELAY_BINS) per-slot deltas; row i's value is the
    p-quantile of slots max(0, i-window+1)..i combined — the windowed-tail
    series the SLO burn rate is judged on.  Windows whose combined rows are
    all zero report NaN (inherited from :func:`hist_percentile`)."""
    if int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    h = np.asarray(hist_rows, np.float64)
    c = h.cumsum(axis=0)
    lo = np.concatenate([np.zeros_like(c[:window]), c[:-window]], axis=0) \
        if window < len(c) else np.zeros_like(c)
    return hist_percentile(c - lo, p)


def _map(d: dict, fn) -> dict:
    return {name: fn(v) for name, v in d.items()}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TimelineBuf:
    """Per-slot series + histogram deltas as device arrays.

    pos:    () int32 slots appended (ring mode; ``capacity`` in windowed
            mode).  Gains leading axes under vmap / :meth:`concat`.
    series: name -> (S,) float32 per-slot values
    hists:  name -> (S, B) int32 per-slot histogram deltas
    ``capacity`` (S) and ``window`` (samples per slot; 1 = per-round ring)
    are static pytree fields — part of the tracing structure, like the
    metric names."""

    pos: jax.Array
    series: dict
    hists: dict
    capacity: int = dataclasses.field(metadata=dict(static=True))
    window: int = dataclasses.field(metadata=dict(static=True))

    @classmethod
    def zeros(cls, capacity: int, series=(), hists=None,
              window: int = 1) -> "TimelineBuf":
        return cls(
            pos=jnp.int32(0),
            series={n: jnp.zeros((int(capacity),), jnp.float32) for n in series},
            hists={n: jnp.zeros((int(capacity), int(b)), jnp.int32)
                   for n, b in dict(hists or {}).items()},
            capacity=int(capacity),
            window=int(window),
        )

    # ---- in-trace updates -------------------------------------------------
    def append(self, values: dict, hist_obs: dict | None = None) -> "TimelineBuf":
        """Write one slot at ``pos % capacity`` (ring semantics).

        ``values``: name -> scalar for the series slots.  ``hist_obs``:
        name -> (bucket_idx, weight) vectors scattered into that slot's
        delta row (pass a 0/1 weight mask to drop padded entries)."""
        i = jnp.mod(self.pos, self.capacity)
        series = dict(self.series)
        for name, v in values.items():
            series[name] = series[name].at[i].set(jnp.asarray(v, jnp.float32))
        hists = dict(self.hists)
        for name, (idx, w) in (hist_obs or {}).items():
            bins = hists[name].shape[-1]
            row = jnp.zeros((bins,), jnp.int32).at[
                jnp.clip(jnp.asarray(idx, jnp.int32), 0, bins - 1)
            ].add(jnp.asarray(w, jnp.int32))
            hists[name] = hists[name].at[i].set(row)
        return dataclasses.replace(self, pos=self.pos + 1, series=series,
                                   hists=hists)

    # ---- folds ------------------------------------------------------------
    def reduce_rows(self, rows: int | None = None) -> "TimelineBuf":
        """Cut the tail padding a chunk launch adds by repeating its last
        real row.  Unlike MetricsBuf this does NOT reduce across cases —
        timelines stay per case; chunks then :meth:`concat`."""

        def cut(a):
            return a[:rows] if rows is not None else a

        return dataclasses.replace(
            self, pos=cut(self.pos), series=_map(self.series, cut),
            hists=_map(self.hists, cut),
        )

    def concat(self, other: "TimelineBuf") -> "TimelineBuf":
        """Stack two per-case timelines along the leading case axis."""
        if (self.capacity, self.window) != (other.capacity, other.window):
            raise ValueError(
                f"cannot concat timelines with different slotting: "
                f"{(self.capacity, self.window)} vs "
                f"{(other.capacity, other.window)}"
            )
        cat = lambda a, b: jnp.concatenate([a, b], axis=0)
        return dataclasses.replace(
            self,
            pos=cat(jnp.atleast_1d(self.pos), jnp.atleast_1d(other.pos)),
            series={n: cat(v, other.series[n]) for n, v in self.series.items()},
            hists={n: cat(v, other.hists[n]) for n, v in self.hists.items()},
        )

    # ---- export -----------------------------------------------------------
    def snapshot(self) -> dict:
        """The one host sync: device arrays -> numpy, ring order restored.

        Ring mode (scalar ``pos``): slots come back oldest-first and cut to
        the appended count.  Windowed/stacked mode (vmapped ``pos``): the
        per-case arrays pass through as-is."""
        pos = np.asarray(self.pos)
        series = {n: np.asarray(v) for n, v in self.series.items()}
        hists = {n: np.asarray(v) for n, v in self.hists.items()}
        if pos.ndim == 0:
            m = int(pos)
            if m <= self.capacity:
                order = np.arange(m)
            else:  # wrapped: oldest slot sits at pos % capacity
                order = (np.arange(self.capacity) + m) % self.capacity
            series = {n: v[order] for n, v in series.items()}
            hists = {n: v[order] for n, v in hists.items()}
            slots = len(order)
        else:
            slots = self.capacity
        return {
            "window": self.window,
            "capacity": self.capacity,
            "slots": slots,
            "pos": pos.tolist(),
            "series": series,
            "hists": hists,
        }


def sweep_timeline(out: dict, interarrivals, *, window: int, valid=None,
                   backlog=None) -> TimelineBuf:
    """Windowed timeline from a scan-core output dict, inside the vmapped
    ``one`` — traced alongside the primary outputs; the launcher cuts the
    tail padding and concatenates per chunk.

    Per window of ``window`` arrivals: ``lam`` (valid arrivals / elapsed
    seconds), ``served`` (valid count), mean ``pick_n``/``pick_k``, the
    optional ``backlog`` series mean, and a ``delay`` histogram delta of
    the total delays under the fixed log buckets.  ``valid`` is the (T,)
    real-arrival mask (bucket padding must not count); all reductions are
    per-slot and leading-batch invariant, so streamed / sharded runs carry
    the identical timeline."""
    if int(window) < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    total = out["total"]
    T = total.shape[-1]
    if T % window:
        raise ValueError(f"horizon {T} not divisible by window {window}")
    S = T // window
    mask = jnp.ones(T, bool) if valid is None else valid
    w = mask.astype(jnp.float32)
    wi = mask.astype(jnp.int32)
    cnt = w.reshape(S, window).sum(axis=1)
    denom = jnp.maximum(cnt, 1.0)

    def wmean(x):
        return (jnp.asarray(x, jnp.float32) * w).reshape(S, window).sum(axis=1) / denom

    span = (jnp.asarray(interarrivals, jnp.float32) * w).reshape(S, window).sum(axis=1)
    lam = jnp.where(span > 0, cnt / jnp.maximum(span, 1e-12), 0.0)
    series = {
        "lam": lam,
        "served": cnt,
        "pick_n": wmean(out["n"]),
        "pick_k": wmean(out["k"]),
    }
    if backlog is not None:
        series["backlog"] = wmean(backlog)
    win_idx = jnp.arange(T) // window
    hist = jnp.zeros((S, DELAY_BINS), jnp.int32).at[
        win_idx, delay_bucket(total)
    ].add(wi)
    return TimelineBuf(pos=jnp.int32(S), series=series,
                       hists={"delay": hist}, capacity=S, window=window)
