"""Shared on-device reduction helpers for the sweep frontiers.

:func:`masked_percentiles` is the single implementation of the
sort-and-gather percentile reduction that used to live twice — inline in
``repro.fleet.frontier`` (unmasked ``jnp.percentile``) and in
``repro.sched.frontier`` (class-masked sort + gather). All three frontier
modules (fleet, sched, taskq) now route through this one:

* values outside ``mask`` are pushed to ``BIG`` before the sort, so they
  sort past every real sample and never enter a gather;
* the gather index is ``floor(q/100 · (count−1))`` — lower-interpolation
  percentiles, exact order statistics of the masked sample (no
  interpolation between neighbors, so the result is always a value that
  actually occurred);
* rows whose mask is empty report NaN (there is no sample to take an order
  statistic of); a single-survivor mask reports that survivor for every q.

:func:`frontier_block_reduce` and :func:`convergence_reduce` are the fused
per-block reduction kernels behind BOTH frontier paths: the materialized
reduction (``repro.fleet.frontier`` over a whole (G, T) result block) and
the streaming per-chunk fold (``repro.fleet.shard``, one (chunk, T) block
at a time). Because the two paths run the *same* jitted functions on the
same per-row data — and per-row reductions are invariant to the leading
batch size — streamed statistics are bit-exact equals of the materialized
ones (asserted in ``tests/test_shard.py``).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp

from repro.core.controller import BIG


def masked_percentiles(x, qs, mask=None):
    """(G, T) values → (G, len(qs)) lower-interpolation percentiles.

    ``mask`` (G, T) bool restricts each row to a subsample (e.g. one class
    of a multi-class stream); ``None`` reduces over whole rows. Rows with an
    empty mask report NaN. Traceable — safe inside jitted reductions.
    """
    qs = jnp.asarray(qs, jnp.float32)
    T = x.shape[1]
    if mask is None:
        cnt = jnp.full((x.shape[0],), T, jnp.int32)
        srt = jnp.sort(x, axis=1)
    else:
        cnt = jnp.sum(mask, axis=1).astype(jnp.int32)
        srt = jnp.sort(jnp.where(mask, x, BIG), axis=1)
    idx = jnp.clip(
        (qs[:, None] / 100.0 * (cnt[None, :] - 1)).astype(jnp.int32), 0, T - 1
    )  # (len(qs), G)
    # An empty subsample has no order statistics: its gather would land on
    # the BIG sentinel (via the idx clamp) — report NaN instead, and let the
    # frontiers propagate it. A single survivor (cnt == 1) needs no special
    # case: every q indexes floor(q/100 · 0) = 0, the survivor itself.
    return jnp.where(
        cnt[:, None] > 0, jnp.take_along_axis(srt, idx.T, axis=1), jnp.nan
    )  # (G, len(qs))


@functools.partial(jax.jit, static_argnames=("w",))
def frontier_block_reduce(out, delta_bar, delta_tilde, psi_bar, psi_tilde,
                          J, *, w: int):
    """One jitted per-row frontier reduction over a (rows, T) result block.

    The single implementation behind the fleet/taskq frontier statistics:
    the materialized path calls it once on the whole (G, T) block, the
    streaming path once per (chunk, T) launch block. Module-level (with the
    warmup cut static) so repeated reductions of same-shaped blocks hit the
    compile cache.
    """
    from repro.core import queueing

    tot = out["total"][:, w:]
    nf = out["n"][:, w:].astype(jnp.float32)
    kf = out["k"][:, w:].astype(jnp.float32)
    r = nf / kf
    params = types.SimpleNamespace(
        delta_bar=delta_bar[:, None], delta_tilde=delta_tilde[:, None],
        psi_bar=psi_bar[:, None], psi_tilde=psi_tilde[:, None],
    )
    usage = queueing.usage(params, J[:, None], kf, r)  # Eq.3, broadcast
    pct = masked_percentiles(tot, [50.0, 90.0, 95.0, 99.0])
    return {
        "mean": jnp.mean(tot, axis=1),
        "std": jnp.std(tot, axis=1),
        "p50": pct[:, 0], "p90": pct[:, 1], "p95": pct[:, 2], "p99": pct[:, 3],
        "mean_queueing": jnp.mean(out["queueing"][:, w:], axis=1),
        "mean_k": jnp.mean(kf, axis=1),
        "mean_n": jnp.mean(nf, axis=1),
        "mean_usage": jnp.mean(usage, axis=1),
    }


@functools.partial(jax.jit, static_argnames=("w", "bins"))
def convergence_reduce(k, *, w: int, bins: int):
    """Per-row adaptation-convergence integers for a (rows, T) k block.

    The device mirror of the host loop in :func:`repro.fleet.frontier.
    convergence_stats`, returning exact integers so the streamed path can
    finish the fractions on host in float64, bit-for-bit equal to the
    numpy originals:

    * ``modal_k`` — first-argmax of the k histogram (``np.bincount(...).
      argmax()`` tie-breaking);
    * ``modal_count`` — occurrences of the modal k;
    * ``settle_idx`` — 1 + the last position where k leaves ±1 of the modal
      value (0 if it never does).

    ``bins`` must exceed every k the block can contain (any table length
    bound works — extra bins hold zero counts and never win the argmax).
    """
    ks = k[:, w:].astype(jnp.int32)
    counts = jnp.sum(ks[:, :, None] == jnp.arange(bins)[None, None, :], axis=1)
    modal = jnp.argmax(counts, axis=1).astype(jnp.int32)  # first max, as bincount
    off = jnp.abs(ks - modal[:, None]) > 1
    pos = jnp.arange(1, ks.shape[1] + 1, dtype=jnp.int32)
    return {
        "modal_k": modal,
        "modal_count": jnp.take_along_axis(counts, modal[:, None], axis=1)[:, 0],
        "settle_idx": jnp.max(jnp.where(off, pos[None, :], 0), axis=1),
    }
