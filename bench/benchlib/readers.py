"""Arithmetic shared by the metric readers in ``bench/metrics``.

A reader that finds nothing to read returns None, and the run leaves its
metric out of the result line.
"""

from __future__ import annotations

from benchlib import stats
from benchlib.work import codec_call_least_s, dense_prefill_flops

#: the fused admission -> MDS decode -> prefill launch (``ClosedLoopServer``)
FUSED_MODULE = "jit_core"
#: one cached decode step (``ServingEngine._decode``)
DECODE_MODULE = "jit_decode_step"
#: the codec's Pallas kernel: the storage path's only Mosaic custom call
CODEC_KERNEL = r"tpu_custom_call"


def latencies_ms(run, op: str) -> list[float]:
    """Due time to answer, per request of ``op``; a failed or unanswered
    request is slower than every answer."""
    out = []
    for r in run.requests:
        if r.op != op:
            continue
        out.append((r.done - r.due) * 1e3 if r.ok and r.done is not None else stats.MISSING)
    return out


def latency_pct(run, op: str, p: float):
    lat = latencies_ms(run, op)
    return stats.percentile(lat, p) if lat else None


def gen_late_p99_ms(run, op: str):
    late = [(r.send - r.due) * 1e3 for r in run.requests if r.op == op]
    return stats.percentile(late, 99) if late else None


def proxy_wait_mean_ms(run, op: str):
    w = [(r.first_start - r.due) * 1e3 for r in run.requests
         if r.op == op and r.first_start is not None]
    return stats.mean(w) if w else None


def device_idle_pct(run):
    tr = run.trace
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * tr.idle_share


def codec_roofline_pct(run):
    """Least time of the codec calls made in the traced window over the
    summed device time of the kernel's events."""
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    kernel_s, n = tr.op_seconds(CODEC_KERNEL)
    calls = [items for t0, t1, _, items in run.codec_calls if run.in_trace(t0)]
    if kernel_s <= 0 or not calls:
        return None
    least = sum(codec_call_least_s(items, run.peaks.flops, run.peaks.hbm_bytes_per_s)
                for items in calls)
    return 100.0 * least / kernel_s


def ttft_ms(run) -> list[float]:
    out = []
    for rd in run.rounds:
        first = (rd.readbacks[0] - rd.send) * 1e3 if rd.readbacks else stats.MISSING
        out += [first] * rd.served + [stats.MISSING] * (rd.requested - rd.served)
    return out


def output_tokens_per_s(run):
    if not run.rounds:
        return None
    tokens = sum(rd.served * stats.count_before(rd.readbacks, run.t_end) for rd in run.rounds)
    return tokens / (run.t_end - run.t0)


def span_mean_ms(run, name: str):
    spans = [(b - a) * 1e3 for a, b in run.spans_named(name) if run.t0 <= a < run.t_end]
    return stats.mean(spans) if spans else None


def module_mean_ms(run, module: str):
    tr = run.trace
    if tr is None or not tr.module_n.get(module):
        return None
    return 1e3 * tr.module_s[module] / tr.module_n[module]


def fused_step_mfu_pct(run):
    """Model FLOPs of the prefill tokens served by the traced fused launches
    over (their device time x the chip's peak)."""
    tr = run.trace
    if tr is None or run.peaks is None or run.model is None:
        return None
    n = tr.module_n.get(FUSED_MODULE, 0)
    traced = [(served, seq) for t, served, seq in run.launches if run.in_trace(t)]
    if not n or not traced:
        return None
    flops = sum(dense_prefill_flops(run.model, served, seq) for served, seq in traced)
    flops *= n / len(traced)  # per launch, as many as the trace holds
    return 100.0 * flops / (tr.module_s[FUSED_MODULE] * run.peaks.flops)
