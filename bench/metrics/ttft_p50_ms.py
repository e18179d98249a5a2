"""Median over rounds of the time from a round's send to its first generated token on the host (a round that left a request unserved is slower than all)."""

from benchlib import readers


def read(run):
    return readers.stats.percentile(readers.ttft_round_ms(run), 50) if run.rounds else None
