"""Mean time of one read decode on the proxy's decoder thread: ``end - start``
of each entry of the program's ``TELEMETRY.decodes`` (one per decoded read),
over the proxies that answered the window's reads, from the window's start to
its last answer."""

from benchlib import program_readers, stats


def read(run):
    tel = program_readers.telemetry()
    decodes = getattr(tel, "decodes", None)
    found = program_readers.results(run, "read", tel)
    if decodes is None or not found:
        return None
    serials = {serial for serial, _ in found}
    t_last = max(res.t_done for _, res in found)
    d = [(end - start) * 1e3 for serial, start, end in list(decodes)
         if serial in serials and run.t0 <= start and end <= t_last]
    return stats.mean(d) if d else None
