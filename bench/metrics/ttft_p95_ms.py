"""95th percentile over all requests of the time from a round's send to its first generated token on the host."""

from benchlib import readers


def read(run):
    return readers.stats.percentile(readers.ttft_ms(run), 95) if run.rounds else None
