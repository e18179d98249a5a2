"""Seconds from the process's start to the window's start: making the data
and weights from the seed, compiling or loading every program the mix runs,
and warming it up."""


def read(run):
    return run.setup_s
