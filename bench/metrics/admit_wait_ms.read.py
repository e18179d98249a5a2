"""Mean time an answered read waits in the proxy's admission queue: arrival
to its tasks going on the task queue (``RequestResult.t_injected``)."""

from benchlib import program_readers


def read(run):
    return program_readers.phase_mean_ms(run, "read", "t_arrival", "t_injected")
