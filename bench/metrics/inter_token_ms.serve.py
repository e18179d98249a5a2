"""Mean host-clock gap between consecutive token readbacks within a round."""

from benchlib import readers


def read(run):
    return readers.inter_token_ms(run)
