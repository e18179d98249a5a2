"""Mean time from an answered read's k-th chunk to its answer: the hand-off
to the proxy's admit loop and the batched reconstruct."""

from benchlib import program_readers


def read(run):
    return program_readers.phase_mean_ms(run, "read", "t_kth", "t_done")
