"""What every run shares: the device gate, the compile cache, compile counting,
the profiler window, and the record a driver hands back.
"""

from __future__ import annotations

import dataclasses
import glob
import shutil
import time
from pathlib import Path

from benchlib import trace as trace_mod
from benchlib.manifest import ROOT

#: persistent compile cache: a fixed path inside the checkout (the program's
#: own ``repro.compile_cache.CACHE_DIR``, ``<checkout>/.jax_cache``)
CACHE_DIR = ROOT / ".jax_cache"
#: scratch for profiler traces, inside the checkout; emptied after each read
TRACE_DIR = ROOT / ".bench_tmp" / "trace"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device_gate(chips: int):
    """The devices of a measurement run; raises unless JAX finds ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)}",
          flush=True)
    if d0.platform != "tpu":
        raise NoChip(f"JAX found platform {d0.platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """Every program goes into the persistent cache, however fast it compiled."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Counts XLA backend compiles (cache hits are not compiles)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0

        def on_event(name, secs, **_):
            if name == self.EVENT:
                self.count += 1
                self.seconds += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)


class Span:
    """A host span of the benchmark's own, written into the profiler's trace
    (``jax.profiler.TraceAnnotation``) and, when ``log`` is given, appended
    to it as (name, start, end) on the host's monotonic clock."""

    def __init__(self, name: str, log: list | None = None):
        from jax.profiler import TraceAnnotation

        self.name, self.log = name, log
        self._ann = TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self._ann.__exit__(*exc)
        if self.log is not None:
            self.log.append((self.name, self.t0, self.t1))
        return False


class Profiler:
    """One traced window: start, stop, reduce, and delete the files."""

    def __init__(self, out_dir: Path = TRACE_DIR):
        self.out_dir = Path(out_dir)
        self.t0 = self.t1 = None
        self.summary: trace_mod.TraceSummary | None = None

    def start(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # spans and XLA events only
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self._ann.__enter__()
        self.t0 = time.monotonic()

    def stop(self) -> trace_mod.TraceSummary:
        import jax

        self.t1 = time.monotonic()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        files = glob.glob(str(self.out_dir / "**" / "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        self.summary = trace_mod.reduce_events(trace_mod.events_from_xspace(files[0]))
        shutil.rmtree(self.out_dir, ignore_errors=True)
        return self.summary

    def covers(self, t: float) -> bool:
        return self.t0 is not None and self.t0 <= t <= (self.t1 or float("inf"))


@dataclasses.dataclass
class Request:
    """One request of an open-loop mix, on the host's monotonic clock."""

    op: str
    due: float
    send: float
    first_start: float | None = None
    done: float | None = None
    ok: bool = False
    #: what the request returned, kept for the check after the window
    answer: object = None
    key_index: int = -1


@dataclasses.dataclass
class Round:
    """One closed-loop serving round."""

    send: float
    readbacks: list  # host time of each generated token's readback
    served: int
    requested: int


@dataclasses.dataclass
class Check:
    """One number compared beside its upper limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Ctx:
    """What a driver needs for one run."""

    workload: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    traffic: dict
    t_proc0: float
    #: the codec to hand the program; None = the program's default, which a
    #: measurement run requires to be the compiled Pallas kernel
    codec: object = None
    #: the process's one CompileCounter
    compiles: CompileCounter | None = None
    #: the devices the run uses (None: jax.devices()[:1])
    devices: list | None = None
    #: calibration only: also read the control's numbers
    control: bool = False


@dataclasses.dataclass
class RunRecord:
    t0: float = 0.0  # the window's start
    t_end: float = 0.0  # the window's close
    setup_s: float = 0.0
    requests: list = dataclasses.field(default_factory=list)
    rounds: list = dataclasses.field(default_factory=list)
    spans: list = dataclasses.field(default_factory=list)  # (name, t0, t1)
    codec_calls: list = dataclasses.field(default_factory=list)  # (t0, t1, kind, items)
    launches: list = dataclasses.field(default_factory=list)  # (t0, served, seq)
    profiler: Profiler | None = None
    model: dict | None = None  # model sizes for FLOP counts
    peaks: object = None
    checks: list = dataclasses.field(default_factory=list)
    control: dict = dataclasses.field(default_factory=dict)  # calibration readings
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int | None = None
    compiles_in_window: int = 0

    @property
    def trace(self) -> trace_mod.TraceSummary | None:
        return self.profiler.summary if self.profiler is not None else None

    def in_trace(self, t: float) -> bool:
        return self.profiler is not None and self.profiler.covers(t)

    def spans_named(self, name: str) -> list[tuple[float, float]]:
        return [(a, b) for n, a, b in self.spans if n == name]


class Phases:
    """Set-up phase times, printed on one line before the window opens."""

    def __init__(self, t_proc0: float):
        self.t = t_proc0
        self.times: dict[str, float] = {}

    def mark(self, name: str) -> None:
        now = time.monotonic()
        self.times[name] = round(now - self.t, 3)
        self.t = now

    def print(self) -> None:
        print(f"set-up phases (s): {self.times}", flush=True)


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
