"""Telemetry demo: every repro.obs layer over one serving loop, one exact
task-level sweep and two profiled kernels.

Turns collection on (:func:`repro.obs.set_enabled` — the programmatic twin
of ``REPRO_OBS=1``), runs a small closed-loop serve and a TaskqSweep grid,
then exports everything the layer produces:

* the device-folded metrics snapshots (round/request counters, picked-(n,k)
  and idle-thread histograms, queue high-water marks) plus their Prometheus
  text exposition;
* the per-round / per-window **timelines** (arrival rate, backlog, picks,
  delay-histogram deltas) and the :func:`repro.obs.slo_report` judged over
  them — burn rate, breach events, controller pick-settling;
* the launch **profiler** table — XLA cost_analysis FLOPs/bytes vs measured
  wallclock, roofline bound per compiled kernel;
* the ASCII **dashboard** (sparkline timelines + SLO tiles + p99 exemplar
  anatomy) on stdout and its self-contained HTML twin, plus the structured
  NDJSON event log;
* the per-request **flight recorder**: the sweep's slowest cell replayed
  with ``flight=True`` (aggregate engines stream, flight replays one case)
  → ``flight_trace.json`` (simulated-clock Perfetto trace, one track per
  pool thread) + ``flight_records.ndjson`` (``repro.obs/flight/v1``), and
  the serving loop's per-round phase ring;
* the shared compile-accounting snapshot across every engine touched;
* a JAX profiler trace of the whole run: the host spans (compile / launch /
  fetch / admission / task / decode boundaries) beside the device's ops on
  one clock, written under ``profile/`` with a ``perfetto_trace.json.gz``
  that Perfetto loads.

Run:  PYTHONPATH=src python examples/obs_demo.py [--fast] [--out DIR]
"""

import argparse
import dataclasses
import glob
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.coding.codec import Codec
from repro.coding.layout import SharedKeyLayout
from repro.configs.qwen1_5_0_5b import CONFIG as QWEN
from repro.core import PAPER_READ_3MB, FeedbackPolicy, RequestClass, StaticPolicy
from repro.core.controller import TofecTables
from repro.core.jax_sim import JaxSimParams, simulate_tofec_scan
from repro.core.static_optimizer import build_class_plan
from repro.core.traces import TraceStore
from repro.fleet import PolicySpec, grid_cases
from repro.models.registry import Arch, _FAMILY_MODULES
from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
from repro.storage import MemoryStore, Proxy
from repro.taskq import TaskqSweep

CLS = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
L = 16
CFG = dataclasses.replace(
    QWEN, name="obs-demo", n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
    d_ff=256, vocab=2048,
)


def serve_rounds(rounds: int, steps: int) -> tuple:
    arch = Arch(cfg=CFG, module=_FAMILY_MODULES["dense"])
    eng = ServingEngine(arch, arch.init(jax.random.key(0)), max_seq=64)
    prompt_len = 16
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    store = MemoryStore()
    rng = np.random.default_rng(0)
    keys = []
    for i in range(4):
        toks = rng.integers(0, CFG.vocab, size=(prompt_len,)).astype(np.int32)
        ServingEngine.store_prompt(store, f"p/{i}", layout, toks)
        keys.append(f"p/{i}")
    proxy = Proxy(store, StaticPolicy(8, 4), L=8,
                  write_policy=FeedbackPolicy(layout.N, layout.K))
    step = FusedServingStep.for_policy(ServePolicy.tofec(), CLS, L,
                                       codec=Codec("jnp"))
    server = ClosedLoopServer(eng, proxy, layout, step, prompt_len=prompt_len)
    try:
        for _ in range(rounds):
            server.serve_round(keys, steps=steps)
        return (server.metrics.snapshot(), server.timeline.snapshot(),
                server.flight.records())
    finally:
        proxy.close()


def taskq_grid(count: int) -> tuple:
    sizes = tuple(CLS.file_mb / k for k in range(1, CLS.k_max + 1))
    store = TraceStore.generate(PAPER_READ_3MB, sizes, threads=CLS.n_max,
                                samples=1024, correlation=0.0, seed=3)
    cases = grid_cases([10.0, 25.0],
                       [PolicySpec.tofec(), PolicySpec.static(12, 6)],
                       [0], CLS, L)
    dp = store.device_pools(n_max=CLS.n_max)
    sweep = TaskqSweep(chunk=4)
    res = sweep.run(cases, count, dp)
    # Flight zoom: replay the grid's slowest cell with the recorder on.
    worst = int(np.argmax(res.to_numpy()["total"].mean(axis=1)))
    log = sweep.replay_flight(res, dp, worst)
    return res.metrics.snapshot(), res.timeline.snapshot(), log


def profile_kernels(count: int) -> None:
    """Roofline-profile the fluid scan and the codec's decode GEMM shape."""
    p = JaxSimParams.from_class(CLS, L)
    tables = TofecTables.from_plan(build_class_plan(CLS, L))
    rng = np.random.default_rng(0)
    inter = jnp.asarray(rng.exponential(1.0 / 25.0, size=count), jnp.float32)
    exps = jnp.asarray(rng.exponential(1.0, size=(count, CLS.n_max)), jnp.float32)
    # Close over the static params: AOT-compiled callables take only the
    # array arguments, so profile a fully-array-signature wrapper.
    scan = jax.jit(lambda i, e: simulate_tofec_scan(p, tables, i, e))
    obs.profile_launch("tofec_scan", scan, inter, exps)

    # The MDS decode inner product at a serving-sized shape: (k × n) decode
    # matrix against n coded strips of 4 KB.
    G = jnp.asarray(rng.standard_normal((CLS.k_max, CLS.n_max)), jnp.float32)
    shards = jnp.asarray(rng.standard_normal((CLS.n_max, 4096)), jnp.float32)
    obs.profile_launch("decode_matmul", jax.jit(lambda a, b: a @ b), G, shards)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true", help="smaller run (CI)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "benchmarks", "results"))
    args = ap.parse_args()

    obs.set_enabled(True)
    obs.reset_profiles()
    out_dir = os.path.abspath(args.out)
    trace_dir = os.path.join(out_dir, "profile")
    shutil.rmtree(trace_dir, ignore_errors=True)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # spans and XLA events, no Python calls
    with jax.profiler.trace(trace_dir, create_perfetto_trace=True,
                            profiler_options=opts):
        serve_snap, serve_tl, serve_flight = serve_rounds(
            rounds=2 if args.fast else 4, steps=2 if args.fast else 4)
        taskq_snap, taskq_tl, flight_log = taskq_grid(
            count=128 if args.fast else 512)
        profile_kernels(count=128 if args.fast else 1024)

        spec = obs.SLOSpec(target_s=0.25, percentile=0.99, window=4)
        events = obs.EventLog("obs_demo")
        exemplars = flight_log.exemplars(3)
        report = obs.slo_report(serve_tl, spec, label="obs_demo", events=events,
                                exemplars=exemplars)
    (trace_path,) = glob.glob(os.path.join(trace_dir, "**", "perfetto_trace.json.gz"),
                              recursive=True)
    profile = obs.profile_snapshot()

    print("== serving metrics ==")
    print(obs.to_prometheus(serve_snap, prefix="repro",
                            labels={"run": "obs_demo", "plane": "serve"}))
    print("== taskq metrics ==")
    print(obs.to_prometheus(taskq_snap, prefix="repro",
                            labels={"run": "obs_demo", "plane": "taskq"}))

    print("== compile accounting ==")
    for label, row in obs.compile_snapshot().items():
        print(f"  {label}: traces={row['traces']} launches={row['launches']}")

    print("\n== dashboard ==")
    print(obs.ascii_dashboard({"serve": serve_tl, "taskq": taskq_tl},
                              slo=report, profile=profile,
                              exemplars=exemplars))

    os.makedirs(out_dir, exist_ok=True)
    snap_path = os.path.join(out_dir, "obs_metrics.json")
    with open(snap_path, "w") as f:
        json.dump({"meta": obs.run_meta(), "serve": serve_snap,
                   "taskq": taskq_snap,
                   "slo": {k: v for k, v in report.items() if k != "events"},
                   "serve_flight": serve_flight,
                   "profile": profile,
                   "compile": obs.compile_snapshot()}, f, indent=1)
    dash_path = obs.html_report(
        os.path.join(out_dir, "obs_dashboard.html"),
        {"serve": serve_tl, "taskq": taskq_tl}, slo=report, profile=profile,
        exemplars=exemplars, meta={"run": "obs_demo", "fast": bool(args.fast)})
    events_path = events.write(os.path.join(out_dir, "obs_events.ndjson"))
    flight_trace = flight_log.write_trace(
        os.path.join(out_dir, "flight_trace.json"))
    flight_recs = flight_log.write_ndjson(
        os.path.join(out_dir, "flight_records.ndjson"))
    print(f"\nwrote {trace_path}")
    print(f"wrote {snap_path}")
    print(f"wrote {dash_path}")
    print(f"wrote {events_path}")
    print(f"wrote {flight_trace}")
    print(f"wrote {flight_recs}")


if __name__ == "__main__":
    main()
