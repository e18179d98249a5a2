"""Mean wall time of a round's prompt fetch (Proxy.read_many, raw chunks)."""

from benchlib import readers


def read(run):
    return readers.span_mean_ms(run, "serve.fetch")
