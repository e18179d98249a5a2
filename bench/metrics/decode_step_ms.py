"""Device time of one cached decode step of the whole batch."""

from benchlib import readers


def read(run):
    return readers.module_mean_ms(run, readers.DECODE_MODULE)
