"""Share of the proxy's L threads' time spent inside store calls
(``Proxy.thread_busy_s``), from the window's start to the last send, in the
proxy that answered the window's reads."""

from benchlib import program_readers


def read(run):
    return program_readers.thread_busy_pct(run, "read")
