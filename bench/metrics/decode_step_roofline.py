"""Share of its roofline one cached decode step reaches: its least time (weights, attended keys and values) over its device time."""

from benchlib import readers


def read(run):
    return readers.decode_step_roofline_pct(run)
