"""Readers of an MoE model's serving run: the routing counters the program
records for each closed-loop round (``repro.serve.engine.ROUTING``), joined
to the benchmark's rounds, and the work they imply over the device trace.

A round's record holds the (token, choice) pairs each held expert took in
the round's prefill and in each decode step whose token was read back, and
those routed to experts held elsewhere (the last column); the readers here
use the prefill's. It is joined to
the benchmark's round by host time: it is appended as the round ends,
after the round's send and before the next round's. A program without
that record, or a round without an entry, gives None.
"""

from __future__ import annotations

import bisect

import numpy as np

from benchlib import stats
from benchlib.moe_work import moe_prefill_flops
from benchlib.readers import FUSED_MODULE, _rounds_with_prompt_len


def routing_record():
    """The program's process-wide routing record, or None where it has none."""
    try:
        from repro.serve.engine import ROUTING
    except ImportError:
        return None
    return ROUTING


def routing_by_round(run, record=None) -> list:
    """Per round of ``run``, (prefill counts (MoE layers, held + 1), decode
    counts (steps, MoE layers, held + 1)) from the program's record, or None."""
    record = record if record is not None else routing_record()
    if record is None:
        return [None] * len(run.rounds)
    entries = sorted(list(record.rounds), key=lambda e: e[0])
    times = [e[0] for e in entries]
    sends = [rd.send for rd in run.rounds] + [float("inf")]
    out = []
    for i, rd in enumerate(run.rounds):
        j = bisect.bisect_left(times, rd.send)
        hit = j < len(times) and times[j] < sends[i + 1]
        out.append((np.asarray(entries[j][1]), np.asarray(entries[j][2])) if hit else None)
    return out


def _scale_to_served(counts, rows: int, served: int) -> float:
    """Pairs on held experts in ``counts`` (..., held + 1), for the served
    rows only: the counters also count the rows that pad the batch."""
    held = float(np.asarray(counts, np.int64)[..., :-1].sum())
    return held * served / rows if rows else held


def _rows(s: dict, counts, tokens_per_row: int) -> int:
    """Rows (sequences) the counters cover: every row makes tokens x top_k
    pairs in each MoE layer."""
    total = int(np.asarray(counts, np.int64).sum())
    per_row = tokens_per_row * s["top_k"] * (s["n_layers"] - s["n_dense_layers"])
    return total // per_row if per_row else 0


def prefill_pairs(s: dict, counts, served: int, seq: int) -> float:
    """Held-expert pairs of a round's prefill, for its served prompts."""
    return _scale_to_served(counts, _rows(s, counts, seq), served)


def prefill_mfu_pct(run, record=None):
    """Model FLOPs of the traced fused launches' prefills (routed work from
    the counters) over (their device time x the chip's peak)."""
    tr = run.trace
    if tr is None or run.peaks is None or run.model is None:
        return None
    n = tr.module_n.get(FUSED_MODULE, 0)
    routing = routing_by_round(run, record)
    flops = []
    for (rd, seq), r in zip(_rounds_with_prompt_len(run), routing):
        if r is not None and run.in_trace(rd.send):
            pairs = prefill_pairs(run.model, r[0], rd.served, seq)
            flops.append(moe_prefill_flops(run.model, rd.served, seq, pairs))
    if not n or not flops:
        return None
    return 100.0 * stats.mean(flops) * n / (tr.module_s[FUSED_MODULE] * run.peaks.flops)


def expert_imbalance(run, record=None):
    """The most prefill pairs any held expert took over the held experts'
    mean, per MoE layer, averaged over the layers of the window's rounds."""
    ratios = []
    for rd, r in zip(run.rounds, routing_by_round(run, record)):
        if r is None or not run.t0 <= rd.send < run.t_end:
            continue
        held = np.asarray(r[0], np.float64)[:, :-1]
        mean = held.mean(axis=1)
        ratios += [m / a for m, a in zip(held.max(axis=1), mean) if a > 0]
    return stats.mean(ratios) if ratios else None
