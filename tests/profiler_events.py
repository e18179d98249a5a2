"""Record a JAX profiler trace on the CPU and read its host events back.

    with profiled(tmp_path) as events:
        ...                      # code that writes spans
    events                       # filled in when the block exits

Each event is ``(name, start_ns, end_ns, stats)``, ``stats`` a dict of the
span's tags.
"""

import contextlib
import glob
import warnings

import jax
from jax.profiler import ProfileData


@contextlib.contextmanager
def profiled(out_dir, *, perfetto: bool = False):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # spans only, no Python function events
    opts.host_tracer_level = 2
    events: list = []
    jax.profiler.start_trace(str(out_dir), create_perfetto_trace=perfetto,
                             profiler_options=opts)
    try:
        yield events
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True)
    with warnings.catch_warnings():  # the stats iterator's type warns on read
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for ev in line.events:
                    events.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))


def named(events, name: str) -> list:
    return [e for e in events if e[0] == name]
