"""Arithmetic shared by the metric readers in ``bench/metrics``.

A reader that finds nothing to read returns None, and the run leaves its
metric out of the result line.
"""

from __future__ import annotations

from benchlib import stats
from benchlib.work import (codec_call_least_s, dense_decode_step_work, dense_prefill_flops,
                           least_s)

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


def ttft_round_ms(run) -> list[float]:
    """Per round, its send to its first token's readback on the host. The
    requests of a round share that readback, so a round counts once; a round
    that left a request unserved is slower than every round."""
    return [(rd.readbacks[0] - rd.send) * 1e3
            if rd.readbacks and rd.served == rd.requested else stats.MISSING
            for rd in run.rounds]


def inter_token_ms(run):
    """Mean gap between consecutive token readbacks of a round, over every
    gap that closes inside the window."""
    gaps = [(b - a) * 1e3 for rd in run.rounds
            for a, b in zip(rd.readbacks, rd.readbacks[1:]) if b < run.t_end]
    return stats.mean(gaps) if gaps else None


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


def _rounds_with_prompt_len(run):
    """(round, prompt length) of each round; the driver appends one launch
    (time, served, prompt length) per round."""
    if len(run.launches) != len(run.rounds):
        raise ValueError("a serving run records one launch per round")
    return [(rd, seq) for rd, (_, _, seq) in zip(run.rounds, run.launches)]


def decode_step_roofline_pct(run):
    """Least time of the traced cached decode steps over their device time.

    Decode call j of a round follows the round's j-th readback and attends
    prompt length + j + 1 positions in each served sequence. The mean least
    time of the calls made inside the traced window, times the number of
    decode modules the trace holds, over those modules' summed time."""
    tr = run.trace
    if tr is None or run.peaks is None or run.model is None:
        return None
    n = tr.module_n.get(DECODE_MODULE, 0)
    least = [least_s(*dense_decode_step_work(run.model, [seq + j + 1] * rd.served),
                     run.peaks.flops, run.peaks.hbm_bytes_per_s)[0]
             for rd, seq in _rounds_with_prompt_len(run)
             for j, t in enumerate(rd.readbacks) if run.in_trace(t)]
    if not n or not least:
        return None
    return 100.0 * stats.mean(least) * n / tr.module_s[DECODE_MODULE]


def serve_mfu_pct(run):
    """Model FLOPs of the work served inside the window over (the window x
    the chip's peak): a round's prefill of its served prompts once its first
    token is read back, and the decode step behind each later token read
    back (token j attends prompt length + j positions)."""
    if run.peaks is None or run.model is None or not run.rounds:
        return None
    flops = 0.0
    for rd, seq in _rounds_with_prompt_len(run):
        for j, t in enumerate(rd.readbacks):
            if t >= run.t_end:
                break
            flops += (dense_prefill_flops(run.model, rd.served, seq) if j == 0 else
                      dense_decode_step_work(run.model, [seq + j] * rd.served)[0])
    return 100.0 * flops / ((run.t_end - run.t0) * run.peaks.flops)
