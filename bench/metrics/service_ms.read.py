"""Mean time from an answered read's first task starting to its k-th chunk
arriving: the k-of-n tasks in the emulated S3 (``RequestResult.t_kth``)."""

from benchlib import program_readers


def read(run):
    return program_readers.phase_mean_ms(run, "read", "t_first_start", "t_kth")
