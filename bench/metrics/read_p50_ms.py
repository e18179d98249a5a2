"""Median read latency, due time to answer."""

from benchlib import readers


def read(run):
    return readers.latency_pct(run, "read", 50)
