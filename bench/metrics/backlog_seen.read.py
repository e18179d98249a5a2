"""Mean backlog the proxy handed its policy when each answered read of the
window was submitted (``RequestResult.q``, TOFEC's q)."""

from benchlib import program_readers, stats


def read(run):
    qs = [res.q for _, res in program_readers.results(run, "read")
          if getattr(res, "q", None) is not None]
    return stats.mean(qs) if qs else None
