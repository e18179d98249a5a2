"""Mean time from a read's due time to its first task starting on a proxy thread."""

from benchlib import readers


def read(run):
    return readers.proxy_wait_mean_ms(run, "read")
