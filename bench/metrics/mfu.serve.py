"""Model FLOPs of the prefills and decode steps served in the window over the window times the bf16 peak."""

from benchlib import readers


def read(run):
    return readers.serve_mfu_pct(run)
