"""99th percentile of how late the generator sent a read (send minus due time)."""

from benchlib import readers


def read(run):
    return readers.gen_late_p99_ms(run, "read")
