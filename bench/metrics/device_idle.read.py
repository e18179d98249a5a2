"""Share of the traced window in which no op ran on the device, read mix."""

from benchlib import readers


def read(run):
    return readers.device_idle_pct(run)
