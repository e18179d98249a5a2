"""99th percentile of read latency, due time to answer, over every read due in the window (a failed read is slower than all)."""

from benchlib import readers


def read(run):
    return readers.latency_pct(run, "read", 99)
