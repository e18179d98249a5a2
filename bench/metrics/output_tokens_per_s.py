"""Generated tokens of served requests read back on the host inside the window, over the window."""

from benchlib import readers


def read(run):
    return readers.output_tokens_per_s(run)
