"""Share of the chunk tasks that answered reads started which they used:
100 x sum of k / sum of ``RequestResult.tasks_started``."""

from benchlib import program_readers


def read(run):
    return program_readers.useful_tasks_pct(run, "read")
