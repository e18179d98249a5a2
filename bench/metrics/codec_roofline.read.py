"""Share of its roofline the codec's Pallas kernel reaches in the traced window of a read mix (decodes)."""

from benchlib import readers


def read(run):
    return readers.codec_roofline_pct(run)
