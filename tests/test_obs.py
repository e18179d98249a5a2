"""repro.obs: collection invariance (bit-identical primary outputs and
pinned compile counts with telemetry on), device-folded histogram
correctness against host recounts, exact taskq cancellation accounting,
span-tree nesting + Chrome-trace JSON validity, the Prometheus formatter,
the shared CompileStats registry, and the perf-gate comparison rules."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.core import PAPER_READ_3MB, RequestClass
from repro.core.traces import TraceStore
from repro.fleet import FleetSweep, PolicySpec, grid_cases
from repro.taskq import TaskqSweep

CLS = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
L = 16
SIZES = tuple(CLS.file_mb / k for k in range(1, CLS.k_max + 1))


@pytest.fixture
def obs_on():
    obs.set_enabled(True)
    obs.reset_trace()
    yield
    obs.set_enabled(None)
    obs.reset_trace()


@pytest.fixture
def obs_off():
    obs.set_enabled(False)
    yield
    obs.set_enabled(None)


def _pools(seed=3, samples=512):
    store = TraceStore.generate(
        PAPER_READ_3MB, SIZES, threads=CLS.n_max, samples=samples,
        correlation=0.0, seed=seed,
    )
    return store.device_pools(n_max=CLS.n_max)


def _grid(n_seeds=2):
    return grid_cases(
        [10.0, 25.0], [PolicySpec.tofec(), PolicySpec.static(12, 6)],
        list(range(n_seeds)), CLS, L,
    )


# ---------------------------------------------------------------------------
# MetricsBuf: host-visible semantics of the device folds
# ---------------------------------------------------------------------------


def test_metricsbuf_count_observe_high_snapshot():
    buf = obs.MetricsBuf.zeros(counters=("c",), hists={"h": 4}, highs=("hi",))
    buf = buf.count("c", 3).count("c")
    buf = buf.observe("h", jnp.array([0, 1, 1, 9]))  # 9 clips to last bucket
    buf = buf.observe("h", jnp.array([2, 2]), weight=jnp.array([1, 0]))
    buf = buf.high("hi", jnp.array([1.5, 7.25, 0.0])).high("hi", 2.0)
    snap = buf.snapshot()
    assert snap["counters"]["c"] == 4
    assert snap["hists"]["h"] == [1, 2, 1, 1]
    assert snap["highs"]["hi"] == 7.25


def test_metricsbuf_reduce_rows_drops_tail_padding():
    buf = obs.MetricsBuf(
        counters={"c": jnp.array([1, 2, 99], jnp.int32)},
        hists={"h": jnp.array([[1, 0], [0, 1], [5, 5]], jnp.int32)},
        highs={"hi": jnp.array([1.0, 3.0, 9.0], jnp.float32)},
    )
    snap = buf.reduce_rows(2).snapshot()
    assert snap["counters"]["c"] == 3
    assert snap["hists"]["h"] == [1, 1]
    assert snap["highs"]["hi"] == 3.0


def test_metricsbuf_merge_unions_disjoint_and_adds_shared():
    a = obs.MetricsBuf.zeros(counters=("x",), highs=("hi",)).count("x", 2)
    b = obs.MetricsBuf.zeros(counters=("x", "y"), highs=("hi",))
    b = b.count("x", 5).count("y", 1).high("hi", 4.0)
    snap = a.merge(b).snapshot()
    assert snap["counters"] == {"x": 7, "y": 1}
    assert snap["highs"]["hi"] == 4.0


def test_prometheus_exposition_shape():
    buf = obs.MetricsBuf.zeros(counters=("reqs",), hists={"q": 3}, highs=("q_hi",))
    buf = buf.count("reqs", 2).observe("q", jnp.array([0, 2, 2])).high("q_hi", 2.0)
    text = buf.to_prometheus(prefix="t")
    assert "# TYPE t_reqs_total counter" in text
    assert "t_reqs_total 2" in text
    # cumulative buckets, +Inf tail, count line
    assert 't_q_bucket{le="0"} 1' in text
    assert 't_q_bucket{le="+Inf"} 3' in text
    assert "t_q_count 3" in text
    assert "t_q_hi 2.0" in text


# ---------------------------------------------------------------------------
# Sweep collection: invariance, padding masks, host recounts
# ---------------------------------------------------------------------------


def test_fleet_collection_invariant_and_histograms_match_host_recount():
    cases, count = _grid(), 300  # pads to a larger pow2 time bucket
    try:
        obs.set_enabled(False)
        base = FleetSweep(chunk=4).run(cases, count)
        obs.set_enabled(True)
        res = FleetSweep(chunk=4).run(cases, count)
    finally:
        obs.set_enabled(None)
    # Primary outputs are bit-identical with collection on.
    for name in base.out:
        np.testing.assert_array_equal(
            np.asarray(base.out[name]), np.asarray(res.out[name]))
    # Collection costs no extra compiles (the collect flag is in the key).
    assert res.compiles == base.compiles
    assert base.metrics is None and res.metrics is not None
    snap = res.metrics.snapshot()
    G = len(cases)
    # Padded steps masked out: exact request/task tallies.
    assert snap["counters"]["fleet_requests"] == G * count
    ks = np.asarray(res.out["k"])[:, :count].astype(int)
    ns = np.asarray(res.out["n"])[:, :count].astype(int)
    assert snap["counters"]["fleet_tasks"] == int(ns.sum())
    np.testing.assert_array_equal(
        snap["hists"]["fleet_pick_k"],
        np.bincount(ks.ravel(), minlength=obs.PICK_BINS))
    np.testing.assert_array_equal(
        snap["hists"]["fleet_pick_n"],
        np.bincount(ns.ravel(), minlength=obs.PICK_BINS))
    assert snap["highs"]["fleet_delay_hi"] == pytest.approx(
        float(np.asarray(res.out["total"])[:, :count].max()), rel=1e-6)


def test_taskq_collection_invariant_with_exact_cancellations(obs_on):
    cases, count = _grid(n_seeds=1), 200
    dp = _pools()
    obs.set_enabled(False)
    base = TaskqSweep(chunk=4).run(cases, count, dp)
    obs.set_enabled(True)
    res = TaskqSweep(chunk=4).run(cases, count, dp)
    for name in base.out:
        np.testing.assert_array_equal(
            np.asarray(base.out[name]), np.asarray(res.out[name]))
    assert res.compiles == base.compiles == 1
    snap = res.metrics.snapshot()
    G = len(cases)
    assert snap["counters"]["taskq_requests"] == G * count
    ns = np.asarray(res.out["n"])[:, :count].astype(int)
    ks = np.asarray(res.out["k"])[:, :count].astype(int)
    c = snap["counters"]
    # Cancel RPCs split exactly into queued vs in-service; ties C == D
    # complete with the request, so the total can undershoot Σ(n−k).
    assert c["taskq_cancelled"] == c["taskq_cancel_queue"] + c["taskq_cancel_service"]
    assert 0 < c["taskq_cancelled"] <= int((ns - ks).sum())
    # Idle-thread histogram counts every real arrival once.
    assert sum(snap["hists"]["taskq_idle"]) == G * count
    assert len(snap["hists"]["taskq_idle"]) == L + 1
    assert snap["highs"]["taskq_q_hi"] >= 0.0


def test_taskq_scan_entry_point_collect_arg(obs_off):
    from repro.taskq.engine import taskq_scan
    from repro.taskq.policies import encode_policy

    case = _grid(n_seeds=1)[0]
    dp = _pools()
    enc = encode_policy(PolicySpec.static(12, 6), CLS, L, CLS.k_max + 1,
                        CLS.n_max + 1, None)
    cfg = {"J": CLS.file_mb, "alpha": enc.alpha, "r_max": enc.r_max,
           "pol": enc.pol, "gk_max": enc.gk_max, "h_k": enc.h_k,
           "h_n": enc.h_n}
    from repro.taskq import taskq_streams
    inter, idx = taskq_streams(case, 64, dp.n_rows)
    off = taskq_scan(cfg, inter, idx, dp.pools, dp.sizes_mb, L=L)
    on = taskq_scan(cfg, inter, idx, dp.pools, dp.sizes_mb, L=L, collect=True)
    assert "obs" not in off and "obs" in on
    for name in off:
        np.testing.assert_array_equal(np.asarray(off[name]), np.asarray(on[name]))


# ---------------------------------------------------------------------------
# Closed-loop serving: device metrics ride the fused step
# ---------------------------------------------------------------------------


def _serve_tokens(rounds=2, steps=2):
    import jax

    from repro.coding.codec import Codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core import FeedbackPolicy, StaticPolicy
    from repro.models import get
    from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
    from repro.storage import MemoryStore, Proxy

    arch = get("qwen1.5-0.5b", smoke=True)
    params = arch.init(jax.random.key(2))
    eng = ServingEngine(arch, params, max_seq=64)
    prompt_len = 16
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    store = MemoryStore()
    rng = np.random.default_rng(6)
    keys = []
    for i in range(3):
        toks = rng.integers(0, arch.cfg.vocab, size=(prompt_len,)).astype(np.int32)
        ServingEngine.store_prompt(store, f"p/{i}", layout, toks)
        keys.append(f"p/{i}")
    proxy = Proxy(store, StaticPolicy(8, 4), L=8,
                  write_policy=FeedbackPolicy(layout.N, layout.K))
    step = FusedServingStep.for_policy(ServePolicy.tofec(), CLS, L,
                                       codec=Codec("jnp"))
    server = ClosedLoopServer(eng, proxy, layout, step, prompt_len=prompt_len)
    try:
        results = [server.serve_round(keys, steps=steps) for _ in range(rounds)]
        return [np.asarray(r.tokens) for r in results], server
    finally:
        proxy.close()


def test_closed_loop_metrics_invariant_and_exact(tmp_path):
    obs.set_enabled(False)
    try:
        toks_off, server_off = _serve_tokens()
    finally:
        obs.set_enabled(None)
    obs.set_enabled(True)
    obs.reset_trace()
    try:
        toks_on, server_on = _serve_tokens()
        # Generated tokens bit-identical with collection on; still one trace.
        for a, b in zip(toks_off, toks_on):
            np.testing.assert_array_equal(a, b)
        assert server_on.traces == server_off.traces == 1
        assert server_off.metrics is None
        snap = server_on.metrics.snapshot()
        c = snap["counters"]
        assert c["serve_rounds"] == 2
        assert c["serve_requested"] == 2 * 3
        assert c["serve_served"] == 2 * 3
        assert c["serve_decode_errors"] == 0
        assert sum(snap["hists"]["serve_batch"]) == 2
        assert sum(snap["hists"]["serve_pick_n"]) == 2
        assert snap["highs"]["serve_q_hi"] >= 0.0
        # The round's host spans export as a loadable Chrome trace.
        names = {ev["name"] for ev in obs.get_tracer().events()}
        assert {"serve.round", "serve.fetch", "serve.launch"} <= names
        path = obs.write_trace(str(tmp_path / "serve_trace.json"))
        doc = json.load(open(path))
        assert any(ev["name"] == "serve.round" for ev in doc["traceEvents"])
        # Prometheus exposition of the same snapshot is well-formed.
        assert "repro_serve_rounds_total 2" in obs.to_prometheus(snap)
    finally:
        obs.set_enabled(None)
        obs.reset_trace()


# ---------------------------------------------------------------------------
# Span tracing
# ---------------------------------------------------------------------------


def test_span_nesting_and_chrome_trace_json(obs_on, tmp_path):
    tr = obs.get_tracer()
    with obs.span("outer", mesh=[1]):
        with obs.span("inner", bucket="(4, 64)"):
            pass
        with obs.span("inner"):
            pass
    by_name: dict = {}
    for ev in tr.events():  # spans record at exit: inner events come first
        by_name.setdefault(ev["name"], []).append(ev)
    (outer,), inners = by_name["outer"], by_name["inner"]
    assert outer["args"]["depth"] == 0
    assert outer["args"]["parent"] is None
    assert all(ev["args"]["depth"] == 1 for ev in inners)
    assert all(ev["args"]["parent"] == "outer" for ev in inners)
    assert inners[0]["args"]["bucket"] == "(4, 64)"
    # Chrome trace_event document: loads back, complete events, µs fields.
    path = obs.write_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    assert len(doc["traceEvents"]) == 3
    for ev in doc["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 0 and "pid" in ev and "tid" in ev
    agg = obs.aggregate()
    assert agg["inner"]["count"] == 2
    assert agg["outer"]["total_us"] >= agg["outer"]["max_us"]
    assert "outer" in tr.format_table()


def test_spans_disabled_record_nothing():
    obs.set_enabled(False)
    obs.reset_trace()
    try:
        with obs.span("never"):
            pass
        assert obs.get_tracer().events() == []
    finally:
        obs.set_enabled(None)


def test_traced_decorator(obs_on):
    calls = []

    @obs.traced("deco.fn", tag=1)
    def fn(x):
        calls.append(x)
        return x + 1

    assert fn(1) == 2 and calls == [1]
    ev = [e for e in obs.get_tracer().events() if e["name"] == "deco.fn"]
    assert len(ev) == 1 and ev[0]["args"]["tag"] == 1


def test_sweep_run_emits_spans(obs_on):
    FleetSweep(chunk=4).run(_grid(n_seeds=1), 64)
    names = {ev["name"] for ev in obs.get_tracer().events()}
    assert {"sweep.chunk", "sweep.launch", "sweep.trace"} <= names


# ---------------------------------------------------------------------------
# Shared compile accounting + run metadata
# ---------------------------------------------------------------------------


def test_compile_stats_registry_and_aliases():
    s = obs.CompileStats(label="test.engine")
    s.traces += 2
    s.launches += 5
    snap = obs.compile_snapshot()
    assert snap["test.engine"]["traces"] == 2
    assert snap["test.engine"]["launches"] == 5
    # Back-compat aliases still resolve to the shared class.
    from repro.coding.codec import CodecStats
    from repro.fleet.sweep import SweepStats
    assert SweepStats is obs.CompileStats and CodecStats is obs.CompileStats


def test_run_meta_fields():
    meta = obs.run_meta(mesh_shape=(2, 4))
    assert meta["schema_version"] == obs.SCHEMA_VERSION
    assert meta["host_cores"] >= 1 and meta["host_devices"] >= 1
    assert meta["mesh_shape"] == [2, 4]
    rev = meta["git_rev"]
    assert rev is None or (isinstance(rev, str) and len(rev) >= 7)


# ---------------------------------------------------------------------------
# Perf gate: comparison rules
# ---------------------------------------------------------------------------


def test_gate_rules(tmp_path):
    from benchmarks import gate

    art = {
        "schema": "repro.fleet/BENCH_fleet/v1",
        "grid_size": 8, "count": 256, "compiles": 1, "launches": 2,
        "capacity_req_s": {"tofec": 30.0},
        "headline": {"delay_gain_vs_basic": 2.5},
    }
    res_dir, base_dir = tmp_path / "res", tmp_path / "base"
    res_dir.mkdir()
    (res_dir / "BENCH_fleet.json").write_text(json.dumps(art))
    # No baseline: passes with a note.
    assert gate.check(str(res_dir), str(base_dir)) == 0
    gate.update(str(res_dir), str(base_dir))
    assert gate.check(str(res_dir), str(base_dir)) == 0
    # Count drift fails exactly; stat drift fails past the tolerance.
    bad = dict(art, compiles=2,
               headline={"delay_gain_vs_basic": 2.5 * 1.2})
    (res_dir / "BENCH_fleet.json").write_text(json.dumps(bad))
    assert gate.check(str(res_dir), str(base_dir)) == 1
    fails, warns, notes = gate.check_file(
        str(res_dir / "BENCH_fleet.json"),
        str(base_dir / "BENCH_fleet.json"))
    assert len(fails) == 2 and not warns
    # Within-tolerance stat drift passes.
    ok = dict(art, headline={"delay_gain_vs_basic": 2.5 * 1.05})
    (res_dir / "BENCH_fleet.json").write_text(json.dumps(ok))
    assert gate.check(str(res_dir), str(base_dir)) == 0


# ---------------------------------------------------------------------------
# TimelineBuf: ring semantics, windows, percentile recovery
# ---------------------------------------------------------------------------


def test_timeline_window_rule():
    # max(T_bucket // TIMELINE_SLOTS, 1): derived from the pow2 time bucket.
    assert obs.timeline_window(64) == 1
    assert obs.timeline_window(8) == 1
    assert obs.timeline_window(512) == 8
    assert obs.timeline_window(1024) == 16


def test_timelinebuf_ring_wrap_restores_order():
    buf = obs.TimelineBuf.zeros(4, series=("x",), hists={"h": 3})
    for i in range(6):
        buf = buf.append({"x": float(i)},
                         {"h": (jnp.array([i % 3]), jnp.array([1]))})
    snap = buf.snapshot()
    # Wrapped ring: the last 4 appends survive, oldest first.
    assert snap["slots"] == 4 and snap["pos"] == 6
    np.testing.assert_array_equal(snap["series"]["x"], [2.0, 3.0, 4.0, 5.0])
    np.testing.assert_array_equal(snap["hists"]["h"].sum(axis=1), [1, 1, 1, 1])
    np.testing.assert_array_equal(
        np.argmax(snap["hists"]["h"], axis=1), [2, 0, 1, 2])


def test_timelinebuf_concat_validates_slotting():
    a = obs.TimelineBuf.zeros(4, series=("x",), window=2)
    b = obs.TimelineBuf.zeros(8, series=("x",), window=2)
    with pytest.raises(ValueError, match="slotting"):
        a.concat(b)


def test_hist_percentile_and_rolling():
    from repro.obs.timeline import bucket_edges

    edges = bucket_edges()
    h = np.zeros((2, obs.DELAY_BINS))
    h[0, 10] = 99
    h[0, 50] = 1
    # p50 of row 0 sits in bucket 10; p999 reaches the lone tail observation.
    p = obs.hist_percentile(h, 0.5)
    assert p[0] == edges[10]
    assert obs.hist_percentile(h, 0.999)[0] == edges[50]
    assert np.isnan(p[1])  # empty row -> NaN, not garbage
    # Rolling window 2: row 1 sees row 0's mass.
    r = obs.rolling_percentile(h, 0.5, window=2)
    assert r[1] == edges[10]


# ---------------------------------------------------------------------------
# Sweep timelines: host recounts, stream/mesh invariance
# ---------------------------------------------------------------------------


def test_fleet_timeline_matches_host_recount(obs_on):
    cases, count = _grid(n_seeds=1), 300  # pads into the pow2 bucket
    res = FleetSweep(chunk=4).run(cases, count)
    assert res.timeline is not None
    snap = res.timeline.snapshot()
    G = len(cases)
    window, S = snap["window"], snap["capacity"]
    T_b = window * S
    assert T_b >= count
    assert snap["series"]["pick_n"].shape == (G, S)
    # Padding is masked: per-case served sums to the real arrival count.
    np.testing.assert_array_equal(snap["series"]["served"].sum(axis=1),
                                  np.full(G, count))
    # Host recount of the windowed pick mean and the delay-histogram deltas.
    # The stacked outputs come back cut to `count`; re-pad to the bucket
    # (padded entries carry zero weight, so the pad value is inert).
    w = (np.arange(T_b) < count).astype(np.float32)
    cnt = w.reshape(S, window).sum(axis=1)
    ns = np.zeros((G, T_b), np.float32)
    ns[:, :count] = np.asarray(res.out["n"], np.float32)
    num = (ns * w).reshape(G, S, window).sum(axis=2)
    expect = np.where(cnt > 0, num / np.maximum(cnt, 1.0), 0.0)
    np.testing.assert_allclose(snap["series"]["pick_n"], expect, rtol=1e-5)
    tot = np.ones((G, T_b), np.float32)
    tot[:, :count] = np.asarray(res.out["total"], np.float32)
    idx = np.asarray(obs.delay_bucket(jnp.asarray(tot)))
    win_idx = np.arange(T_b) // window
    for g in range(G):
        h = np.zeros((S, obs.DELAY_BINS), np.int64)
        np.add.at(h, (win_idx, idx[g]), w.astype(np.int64))
        np.testing.assert_array_equal(snap["hists"]["delay"][g], h)


def test_fleet_streamed_timeline_bit_exact(obs_on):
    cases, count = _grid(n_seeds=1), 256
    mat = FleetSweep(chunk=2).run(cases, count)
    st = FleetSweep(chunk=2).run(cases, count, stream=True)
    a, b = mat.timeline.snapshot(), st.timeline.snapshot()
    assert set(a["series"]) == set(b["series"])
    for name in a["series"]:
        np.testing.assert_array_equal(a["series"][name], b["series"][name])
    np.testing.assert_array_equal(a["hists"]["delay"], b["hists"]["delay"])


def test_taskq_timeline_backlog_series(obs_on):
    cases, count = _grid(n_seeds=1), 200
    res = TaskqSweep(chunk=4).run(cases, count, _pools())
    snap = res.timeline.snapshot()
    G = len(cases)
    assert "backlog" in snap["series"]  # the scan's exact per-arrival queue
    np.testing.assert_array_equal(snap["series"]["served"].sum(axis=1),
                                  np.full(G, count))
    assert (snap["series"]["backlog"] >= 0).all()
    assert snap["hists"]["delay"].sum() == G * count


def test_sweep_timeline_rejects_bad_window():
    out = {"total": jnp.ones(10), "n": jnp.ones(10), "k": jnp.ones(10)}
    with pytest.raises(ValueError, match="not divisible"):
        obs.sweep_timeline(out, jnp.ones(10), window=3)


# ---------------------------------------------------------------------------
# Serve timeline + SLO/convergence monitor
# ---------------------------------------------------------------------------


def test_serve_timeline_and_slo_report():
    obs.set_enabled(True)
    obs.reset_trace()
    try:
        toks, server = _serve_tokens(rounds=3)
        assert server.traces == 1  # the collect variant still compiles once
        snap = server.timeline.snapshot()
        assert snap["window"] == 1 and snap["slots"] == 3
        np.testing.assert_array_equal(snap["series"]["served"], [3, 3, 3])
        np.testing.assert_array_equal(snap["hists"]["delay"].sum(axis=1),
                                      [3, 3, 3])
        assert (snap["series"]["pick_n"] >= snap["series"]["pick_k"]).all()
        spec = obs.SLOSpec(target_s=60.0, percentile=0.99, window=2)
        report = obs.slo_report(snap, spec, label="t")
        conv = report["convergence"]
        assert conv["settled"] and 0 <= conv["settle_slot"] < 3
        assert conv["dwell_final"] > 0
        assert report["max_burn_rate"] == 0.0  # nothing violates a 60 s target
        assert report["percentile_last_s"] > 0
        kinds = [e["kind"] for e in report["events"].events]
        assert "controller_converged" in kinds and "slo_breach" not in kinds
    finally:
        obs.set_enabled(None)
        obs.reset_trace()


def test_serve_timeline_absent_when_disabled(obs_off):
    _, server = _serve_tokens(rounds=1)
    assert server.timeline is None


def test_slo_burn_rate_and_breach_events(obs_on, tmp_path):
    S = 8
    hist = np.zeros((S, obs.DELAY_BINS), int)
    hist[:4, 0] = 100                      # fast slots
    hist[4:, obs.DELAY_BINS - 1] = 100     # every request blows the target
    snap = {"window": 1, "capacity": S, "slots": S, "pos": S,
            "series": {"pick_n": np.full(S, 8.0), "pick_k": np.full(S, 4.0)},
            "hists": {"delay": hist}}
    spec = obs.SLOSpec(target_s=1.0, percentile=0.99, window=2)
    events = obs.EventLog("synthetic")
    report = obs.slo_report(snap, spec, label="synthetic", events=events)
    burn = np.asarray(report["burn_rate"])
    assert (burn[:4] == 0).all() and (burn[4:] >= 1.0).all()
    assert report["breach_slots"] == 4
    kinds = [e["kind"] for e in events.events]
    assert kinds.count("slo_breach") == 1  # one edge event, not 4
    conv = report["convergence"]
    assert conv == {"settle_slot": 0, "settled": True, "final_code": [8, 4],
                    "dwell": {"8/4": 1.0}, "dwell_final": 1.0}
    # NDJSON export: one schema-tagged object per line.
    path = events.write(str(tmp_path / "events.ndjson"))
    lines = [json.loads(ln) for ln in open(path)]
    assert all(ev["schema"] == "repro.obs/event/v1" for ev in lines)
    assert {ev["kind"] for ev in lines} == {"slo_breach", "controller_converged"}
    # Breach events mirror into the span trace as instant marks.
    marks = [e for e in obs.get_tracer().events() if e.get("ph") == "i"]
    assert any(e["name"] == "obs.slo_breach" for e in marks)


def test_slo_recovery_edge():
    hist = np.zeros((6, obs.DELAY_BINS), int)
    hist[1, obs.DELAY_BINS - 1] = 100  # breach slot 1, recover when it ages out
    hist[2:, 0] = 100
    snap = {"window": 1, "capacity": 6, "slots": 6, "pos": 6,
            "series": {"pick_n": np.full(6, 4.0), "pick_k": np.full(6, 2.0)},
            "hists": {"delay": hist}}
    report = obs.slo_report(snap, obs.SLOSpec(target_s=1.0, window=1),
                            label="edge")
    kinds = [e["kind"] for e in report["events"].events]
    assert kinds.count("slo_breach") == 1 and kinds.count("slo_recovered") == 1


# ---------------------------------------------------------------------------
# Prometheus exposition hygiene
# ---------------------------------------------------------------------------


def test_prometheus_help_type_and_label_escaping():
    buf = obs.MetricsBuf.zeros(counters=("reqs",), hists={"q": 2}, highs=("hi",))
    buf = buf.count("reqs", 1).observe("q", jnp.array([0])).high("hi", 1.0)
    text = buf.to_prometheus(prefix="t", labels={"run": 'a"b\\c\nd'})
    assert "# HELP t_reqs_total Running count of 'reqs'." in text
    assert "# TYPE t_reqs_total counter" in text
    assert "# TYPE t_q histogram" in text
    assert "# TYPE t_hi gauge" in text
    esc = 'run="a\\"b\\\\c\\nd"'
    assert "t_reqs_total{" + esc + "} 1" in text
    assert "t_q_bucket{" + esc + ',le="0"} 1' in text
    assert "t_q_count{" + esc + "} 1" in text
    # No labels: bare sample names, headers still present.
    bare = buf.to_prometheus(prefix="t")
    assert "t_reqs_total 1" in bare and "# TYPE t_q histogram" in bare


# ---------------------------------------------------------------------------
# Trace hygiene: unclosed spans, instant marks
# ---------------------------------------------------------------------------


def test_unclosed_spans_autoclose_and_warn_once(obs_on, tmp_path):
    import warnings

    sp1 = obs.span("dangling.outer", tag=1)
    sp1.__enter__()
    sp2 = obs.span("dangling.inner")
    sp2.__enter__()
    with pytest.warns(RuntimeWarning, match="dangling"):
        path = obs.write_trace(str(tmp_path / "t.json"))
    doc = json.load(open(path))
    bad = {e["name"]: e for e in doc["traceEvents"]
           if e["args"].get("incomplete")}
    assert set(bad) == {"dangling.outer", "dangling.inner"}
    assert bad["dangling.outer"]["args"]["tag"] == 1
    # The late real exits are no-ops; a second export neither warns again
    # nor duplicates the auto-closed records.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        sp2.__exit__(None, None, None)
        sp1.__exit__(None, None, None)
        sp3 = obs.span("dangling.late")
        sp3.__enter__()
        path2 = obs.write_trace(str(tmp_path / "t2.json"))
    doc2 = json.load(open(path2))
    names = [e["name"] for e in doc2["traceEvents"]]
    assert names.count("dangling.outer") == 1
    assert "dangling.late" in names


def test_instant_marks_export_and_skip_aggregate(obs_on, tmp_path):
    obs.instant("mark.one", detail="x")
    with obs.span("real"):
        pass
    doc = json.load(open(obs.write_trace(str(tmp_path / "t.json"))))
    marks = [e for e in doc["traceEvents"] if e["ph"] == "i"]
    assert len(marks) == 1 and marks[0]["args"]["detail"] == "x"
    agg = obs.aggregate()  # duration table ignores the durationless marks
    assert "real" in agg and "mark.one" not in agg


# ---------------------------------------------------------------------------
# Launch profiler
# ---------------------------------------------------------------------------


def test_profile_launch_records_and_registers(obs_on):
    import jax

    obs.reset_profiles()
    try:
        fn = jax.jit(lambda a, b: a @ b)
        a = jnp.ones((64, 64), jnp.float32)
        rec = obs.profile_launch("mm", fn, a, a, warmup=1, iters=2)
        assert rec["flops"] > 0 and rec["wall_s"] > 0
        # Peaks come from the device_kind table; a device outside it (the
        # CPU here) gets no roofline rather than another chip's numbers.
        from repro.launch.roofline import PEAKS

        if jax.devices()[0].device_kind in PEAKS:
            assert rec["bound"] in ("compute", "memory")
            assert rec["frac_peak"] > 0
        else:
            assert rec["bound"] is None and rec["frac_peak"] is None
        assert rec["gflops"] > 0 and rec["intensity"] > 0
        snap = obs.profile_snapshot()
        assert snap["mm"]["traces"] == 1
        assert snap["mm"]["launches"] == 3  # warmup + iters
        # First-class citizen of the shared compile registry.
        assert obs.compile_snapshot()["profile.mm"]["launches"] == 3
        table = obs.format_profile()
        assert "mm" in table and "bound" in table
        # Repeat at the same label: counts accumulate, record refreshes.
        obs.profile_launch("mm", fn, a, a, warmup=0, iters=1)
        assert obs.profile_snapshot()["mm"]["launches"] == 4
    finally:
        obs.reset_profiles()


# ---------------------------------------------------------------------------
# Dashboard rendering
# ---------------------------------------------------------------------------


def _ring_snap(rounds=6):
    buf = obs.TimelineBuf.zeros(8, series=("lam", "pick_n", "pick_k", "served"),
                                hists={"delay": obs.DELAY_BINS})
    for i in range(rounds):
        buf = buf.append(
            {"lam": 1.0 + i, "pick_n": 8.0, "pick_k": 4.0, "served": 3.0},
            {"delay": (jnp.array([5, 20, 40]), jnp.array([1, 1, 1]))})
    return buf.snapshot()


def test_ascii_dashboard_renders(obs_on):
    snap = _ring_snap()
    report = obs.slo_report(snap, obs.SLOSpec(target_s=10.0, window=2))
    text = obs.ascii_dashboard({"serve": snap}, slo=report)
    assert "timeline: serve" in text and "lam" in text
    assert "delay_p99_s" in text and "slo" in text


def test_sparkline_shapes():
    assert len(obs.sparkline([1.0, 2.0, 3.0])) == 3
    assert len(obs.sparkline(np.arange(200.0))) == 48
    assert obs.sparkline([np.nan, 1.0])[0] == " "


def test_html_report_self_contained(obs_on, tmp_path):
    snap = _ring_snap()
    report = obs.slo_report(snap, obs.SLOSpec(target_s=10.0, window=2))
    path = obs.html_report(str(tmp_path / "dash.html"), {"serve": snap},
                           slo=report, meta={"run": "test"})
    html = open(path).read()
    assert "<svg" in html and "serve" in html
    assert "prefers-color-scheme: dark" in html  # dual-mode palette
    assert "<script" in html
    # Self-contained: no external fetches.
    assert "https://" not in html and "http://" not in html


# ---------------------------------------------------------------------------
# Perf gate: serve SLO fields
# ---------------------------------------------------------------------------


def test_gate_serve_slo_fields(tmp_path):
    from benchmarks import gate

    art = {
        "schema": "repro.serve/BENCH_serve/v1",
        "rounds": 2, "steps": 2, "prompt_len": 16,
        "results": [{"batch": 4, "fused_req_per_s": 100.0, "speedup": 1.1}],
        "slo": {"settle_round": 1, "dwell_final": 0.5,
                "max_burn_rate": 0.0, "p99_last": 0.02},
    }
    m = gate.normalize(art)
    # Settle round is structurally deterministic -> count class; dwell is a
    # simulation statistic -> stat class (±10%).
    assert m["slo/settle_round"]["kind"] == "count"
    assert m["slo/dwell_final"]["kind"] == "stat"
    res_dir, base_dir = tmp_path / "res", tmp_path / "base"
    res_dir.mkdir()
    (res_dir / "BENCH_serve.json").write_text(json.dumps(art))
    gate.update(str(res_dir), str(base_dir))
    assert gate.check(str(res_dir), str(base_dir)) == 0
    # Settle-round drift fails exactly; dwell within tolerance passes.
    drift = dict(art, slo=dict(art["slo"], settle_round=2, dwell_final=0.52))
    (res_dir / "BENCH_serve.json").write_text(json.dumps(drift))
    fails, warns, notes = gate.check_file(
        str(res_dir / "BENCH_serve.json"), str(base_dir / "BENCH_serve.json"))
    assert len(fails) == 1 and "settle_round" in fails[0]
