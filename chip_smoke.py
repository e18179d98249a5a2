#!/usr/bin/env python3
"""Smoke run of the TOFEC main path on a TPU, through the normal entry points.

    python chip_smoke.py              # one chip: phases 1-5 below
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded sweep only

One process, all data from ``--seed``, nothing read from outside the
repository. Each phase checks its output against a plain reference and the
first failure ends the run with a non-zero exit:

1. device gate — JAX must find a TPU; any other platform is refused before
   a phase runs.
2. storage — the paper's 3 MB class: ``Proxy`` (L=16) over
   ``FaultyStore(LatencyStore(MemoryStore))`` with ~10% read failures and
   the paper's S3 read/write delay constants. 32 objects of 3 MiB
   (``SharedKeyLayout(K=6, r=2, 512 KiB strips)``, codes up to (12, 6)) are
   written with ``Proxy.write``, flushed, and read back with ``read_many``;
   payloads must match and sampled coded objects must equal the numpy
   oracle's strips bit for bit. The default codec must be the compiled
   Pallas kernel.
3. serving — qwen1.5-0.5b at published widths (random weights from the
   seed): prompts stored as coded objects are served by
   ``ClosedLoopServer`` (batch 8, 512-token prompts, 16 decode steps, 3
   rounds). Decoded prompts must equal the stored tokens exactly, and the
   first generated token's logits must agree with the plain
   ``Arch.prefill`` on the same prompts within ``LOGIT_RTOL`` of the
   reference logits' largest magnitude (bf16 weights and activations).
4. checkpoint — the served parameters are saved through the default codec,
   n - k strips of every leaf are lost, and the restore must be bit-exact.
5. exact engine — a 64-case ``TaskqSweep`` grid; one static grid point must
   match ``repro.core.simulator.simulate`` within the tolerance the test
   suite uses (rtol 1e-3, atol 2e-3).

``--chips 4`` runs only the path users run across chips: the phase-5 grid
streamed over a 4-device grid mesh, compared bit for bit with the same grid
on one device, plus a check that the sharded outputs live on all four
devices.

Each phase prints one line with its sizes, wall time and compile time; these
are smoke timings, not measurements. The last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MIB = 2**20

# Phase sizes. Storage: the paper's 3 MB class.
OBJECTS = 32  # 3 MiB objects written and read back
STRIP_BYTES = MIB // 2  # K=6 strips of 512 KiB
ORACLE_SAMPLES = 4  # stored objects compared with the numpy oracle's strips
TIME_SCALE = 1e-2  # emulated S3 delays, scaled
# Serving: qwen1.5-0.5b at published widths.
MODEL = "qwen1.5-0.5b"
BATCH, PROMPT_LEN, DECODE_STEPS, ROUNDS = 8, 512, 16, 3
# Exact engine: 8 rates x 4 policies x 2 seeds = 64 cases.
RATES, ARRIVALS, TRACE_SAMPLES = 8, 2000, 2048
MESH_DEVICES = 4

#: Closed-loop vs plain-prefill logits: max |diff| <= LOGIT_RTOL * max |ref|.
#: Both run the same bf16 prefill; on the chip they agreed bit for bit.
#: Weights rounded through float8_e4m3 move the logits by about 4% of max|ref|.
LOGIT_RTOL = 1e-2
#: Engine vs event oracle, as in tests/test_taskq.py.
ORACLE_RTOL, ORACLE_ATOL = 1e-3, 2e-3


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileClock:
    """Sums XLA backend compile time, so each phase can report its share."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.total = 0.0

        def on_event(name, secs, **_):
            if name == self.EVENT:
                self.total += secs

        jax.monitoring.register_event_duration_secs_listener(on_event)


class Phase:
    """Times one phase; prints its smoke-timing line when it ends cleanly."""

    def __init__(self, clock: CompileClock, name: str):
        self.clock, self.name = clock, name

    def __enter__(self):
        self.t0, self.c0 = time.monotonic(), self.clock.total
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            print(f"[smoke timing, not a measurement] {self.name}: {self.sizes}; "
                  f"wall {time.monotonic() - self.t0:.1f} s, "
                  f"compile {self.clock.total - self.c0:.1f} s", flush=True)
        return False


def device_gate(chips: int):
    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} count={len(devs)}",
          flush=True)
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found platform {d0.platform!r}, not a TPU; "
                         "this run is for the chip only")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                         f"JAX found {len(devs)}")
    return devs


def check_default_codec(codec) -> None:
    check(codec.name == "pallas" and codec.backend.interpret is False,
          f"default codec is {codec.name!r} "
          f"(interpret={getattr(codec.backend, 'interpret', None)}), "
          "expected the compiled Pallas kernel")


# ---------------------------------------------------------------------------
# phase 2: storage
# ---------------------------------------------------------------------------


def phase_storage(clock, seed: int) -> None:
    from repro.coding.codec import get_codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core import PAPER_READ_3MB, PAPER_WRITE_3MB, RequestClass, TOFECPolicy
    from repro.storage import FaultyStore, LatencyStore, MemoryStore, Proxy

    layout = SharedKeyLayout(K=6, r=2, strip_bytes=STRIP_BYTES)
    with Phase(clock, "storage") as ph:
        rng = np.random.default_rng(seed)
        payloads = rng.integers(0, 256, (OBJECTS, layout.file_bytes), dtype=np.uint8)
        inner = MemoryStore()
        store = FaultyStore(
            LatencyStore(inner, PAPER_READ_3MB, PAPER_WRITE_3MB,
                         time_scale=TIME_SCALE, seed=seed),
            p_fail=0.1, seed=seed + 1,
        )
        cls = RequestClass("read3mb", layout.file_bytes / MIB, PAPER_READ_3MB,
                           k_max=layout.K, r_max=float(layout.r), n_max=layout.N)
        proxy = Proxy(store, TOFECPolicy.for_classes([cls], L=16), L=16)
        try:
            check_default_codec(proxy.codec)
            keys = [f"obj/{i}" for i in range(OBJECTS)]
            for key, p in zip(keys, payloads):
                res = proxy.write(key, layout, p.tobytes())
                check(res.ok, f"write {key} failed")
            proxy.flush_writes()
            results = proxy.read_many(keys, layout, layout.file_bytes)
            failed_reads = 0
            for _ in range(5):  # a read can exhaust its n - k failure budget
                bad = [i for i, r in enumerate(results) if not r.ok]
                failed_reads += len(bad)
                if not bad:
                    break
                redo = proxy.read_many([keys[i] for i in bad], layout, layout.file_bytes)
                for i, r in zip(bad, redo):
                    results[i] = r
            for key, p, r in zip(keys, payloads, results):
                check(r.ok, f"read {key} failed after retries")
                check(r.data == p.tobytes(), f"read {key}: payload differs from the write")
            codes = sorted({(r.n, r.k) for r in proxy.results if r.op == "write"})
            read_codes = sorted({(r.n, r.k) for r in results})
        finally:
            proxy.close()
        oracle = get_codec("numpy")
        for i in rng.choice(OBJECTS, size=ORACLE_SAMPLES, replace=False):
            obj = np.frombuffer(inner.get(keys[i]), np.uint8).reshape(-1, STRIP_BYTES)
            want = oracle.encode(payloads[i].reshape(layout.K, STRIP_BYTES),
                                 layout.N, layout.K)
            check(np.array_equal(obj, want[: obj.shape[0]]),
                  f"{keys[i]}: stored strips differ from the numpy oracle's")
        ph.sizes = (f"{OBJECTS} objects x {layout.file_bytes / MIB:g} MiB "
                    f"(K=6, r=2, {STRIP_BYTES // 1024} KiB strips), write codes {codes}, "
                    f"read codes {read_codes}, {failed_reads} reads retried, "
                    f"{ORACLE_SAMPLES} objects match the numpy oracle")


# ---------------------------------------------------------------------------
# phase 3: serving
# ---------------------------------------------------------------------------


def phase_serving(clock, seed: int):
    """Returns the served parameters (phase 4 checkpoints them)."""
    import jax
    import jax.numpy as jnp

    from repro.coding.codec import get_codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core import PAPER_READ_3MB, RequestClass, TOFECPolicy
    from repro.models.registry import get
    from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
    from repro.storage import LatencyStore, MemoryStore, Proxy

    arch = get(MODEL)
    cfg = arch.cfg
    with Phase(clock, "serving") as ph:
        params = jax.jit(arch.init)(jax.random.key(seed))
        engine = ServingEngine(arch, params, max_seq=PROMPT_LEN + DECODE_STEPS)
        # 4-byte tokens over K=4 strips: one strip per PROMPT_LEN/4 tokens.
        layout = SharedKeyLayout(K=4, r=2, strip_bytes=PROMPT_LEN)
        rng = np.random.default_rng(seed + 2)
        prompts = rng.integers(0, cfg.vocab, (ROUNDS * BATCH, PROMPT_LEN), dtype=np.int32)
        inner = MemoryStore()
        keys = [f"prompt/{i}" for i in range(len(prompts))]
        for key, toks in zip(keys, prompts):
            ServingEngine.store_prompt(inner, key, layout, toks)
        cls = RequestClass("prompt", layout.file_bytes / MIB, PAPER_READ_3MB,
                           k_max=layout.K, r_max=float(layout.r), n_max=layout.N)
        codec = get_codec()
        check_default_codec(codec)
        step = FusedServingStep.for_policy(ServePolicy.tofec(), cls, 16, codec=codec)
        proxy = Proxy(LatencyStore(inner, PAPER_READ_3MB, time_scale=TIME_SCALE, seed=seed),
                      TOFECPolicy.for_classes([cls], L=16), L=16)
        ref_prefill = jax.jit(lambda p, t: arch.prefill(p, {"tokens": t},
                                                        max_seq=engine.max_seq))
        srv = ClosedLoopServer(engine, proxy, layout, step, prompt_len=PROMPT_LEN)
        worst = 0.0
        served = 0
        try:
            for rnd in range(ROUNDS):
                rkeys = keys[rnd * BATCH:(rnd + 1) * BATCH]
                res = srv.serve_round(rkeys, steps=DECODE_STEPS)
                G = len(res.served_keys)
                check(G > 0, f"round {rnd}: nothing served")
                idx = [keys.index(k) for k in res.served_keys]
                want = prompts[idx]
                got = np.asarray(res.prompts)[:G]
                check(np.array_equal(got, want),
                      f"round {rnd}: decoded prompt tokens differ from the stored ones")
                check(res.tokens.shape == (G, DECODE_STEPS)
                      and ((res.tokens >= 0) & (res.tokens < cfg.vocab)).all(),
                      f"round {rnd}: generated tokens out of range")
                ref, _ = ref_prefill(params, jnp.asarray(want))
                ref = np.asarray(ref, np.float32)
                cl = np.asarray(res.first_logits, np.float32)[:G]
                check(np.isfinite(cl).all() and np.isfinite(ref).all(),
                      f"round {rnd}: non-finite logits")
                diff = float(np.abs(cl - ref).max())
                scale = float(np.abs(ref).max())
                check(diff <= LOGIT_RTOL * scale,
                      f"round {rnd}: first-token logits differ by {diff:.4g} "
                      f"(> {LOGIT_RTOL} x {scale:.4g})")
                worst = max(worst, diff / scale)
                served += G
        finally:
            proxy.close()
        ph.sizes = (f"{cfg.name} ({cfg.n_layers}L, d_model {cfg.d_model}, vocab {cfg.vocab}), "
                    f"{ROUNDS} rounds x batch {BATCH}, prompt_len {PROMPT_LEN}, "
                    f"{DECODE_STEPS} decode steps, {served} prompts served, "
                    f"{srv.traces} trace(s); "
                    f"logits max|diff|/max|ref| = {worst:.3g} (limit {LOGIT_RTOL})")
    return params


# ---------------------------------------------------------------------------
# phase 4: checkpoint
# ---------------------------------------------------------------------------


def phase_checkpoint(clock, seed: int, params) -> None:
    import jax

    from repro.ckpt.checkpoint import restore_checkpoint, save_checkpoint
    from repro.coding.codec import get_codec
    from repro.storage import FaultyStore, MemoryStore

    with Phase(clock, "checkpoint") as ph:
        check_default_codec(get_codec())
        store = FaultyStore(MemoryStore())
        manifest = save_checkpoint(store, "ckpt", 1, params)
        rng = np.random.default_rng(seed + 3)
        lost = 0
        for name, meta in manifest["leaves"].items():
            n, k = meta["n"], meta["k"]
            for si in rng.choice(n, size=n - k, replace=False):
                store.lose_object(f"ckpt/step1/{name}/strip{si}")
                lost += 1
        restored = restore_checkpoint(store, "ckpt", 1, params)
        leaves = jax.tree.leaves(params)
        back = jax.tree.leaves(restored)
        check(len(leaves) == len(back), "restored tree has a different leaf count")
        nbytes = 0
        for a, b in zip(leaves, back):
            a = np.asarray(a)
            nbytes += a.nbytes
            check(a.dtype == b.dtype and a.shape == b.shape
                  and a.tobytes() == np.asarray(b).tobytes(),
                  "restored leaf differs from the saved one")
        big = max(manifest["leaves"].values(), key=lambda m: m["bytes"])
        codes = sorted({(m["n"], m["k"]) for m in manifest["leaves"].values()})
        ph.sizes = (f"{len(leaves)} leaves, {nbytes / MIB:.1f} MiB, codes {codes}, "
                    f"{lost} strips lost, largest leaf {big['bytes'] / MIB:.1f} MiB "
                    f"({big['strip_bytes'] / MIB:.1f} MiB strips); restore bit-exact")


# ---------------------------------------------------------------------------
# phase 5: exact engine (and the four-chip sharded path)
# ---------------------------------------------------------------------------


def taskq_setup(seed: int):
    from repro.core import PAPER_READ_3MB, RequestClass
    from repro.core.traces import TraceStore
    from repro.fleet import PolicySpec, grid_cases

    cls = RequestClass("read3mb", 3.0, PAPER_READ_3MB, k_max=6, r_max=2.0, n_max=12)
    sizes = tuple(cls.file_mb / k for k in range(1, cls.k_max + 1))
    pools = TraceStore.generate(PAPER_READ_3MB, sizes, threads=cls.n_max, samples=TRACE_SAMPLES,
                                correlation=0.14, seed=seed).device_pools(n_max=cls.n_max)
    policies = [PolicySpec.tofec(), PolicySpec.static(1, 1), PolicySpec.static(12, 6),
                PolicySpec.greedy()]
    cases = grid_cases(np.linspace(6.0, 48.0, RATES), policies, [seed, seed + 1], cls, 16)
    return cls, pools, cases


def phase_taskq(clock, seed: int) -> None:
    from repro.core import StaticPolicy
    from repro.core.simulator import simulate
    from repro.taskq import TaskqSweep, taskq_streams

    with Phase(clock, "exact engine") as ph:
        cls, pools, cases = taskq_setup(seed)
        res = TaskqSweep(chunk=64).run(cases, ARRIVALS, pools)
        out = res.to_numpy()
        check(np.isfinite(out["total"]).all(), "non-finite delays in the grid")
        i = next(j for j, c in enumerate(cases) if c.policy.name == "static(12,6)")
        case = cases[i]
        inter, idx = taskq_streams(case, ARRIVALS, pools.n_rows)
        host = simulate(StaticPolicy(12, 6), np.cumsum(inter.astype(np.float64)),
                        pools.host_sampler(cls.file_mb, idx), L=case.L, warmup_frac=0.0)
        for name, want in (("total", host.totals()), ("queueing", host.queueing()),
                           ("service", host.service())):
            got = out[name][i]
            check(np.allclose(got, want, rtol=ORACLE_RTOL, atol=ORACLE_ATOL),
                  f"grid point {i} ({case.policy.name}): {name} differs from the "
                  f"event oracle by up to {np.abs(got - want).max():.3g}")
        ph.sizes = (f"{len(cases)} cases x {ARRIVALS} arrivals, {res.launches} launch(es), "
                    f"{res.compiles} compile(s); point {i} ({case.policy.name}, "
                    f"lam={case.lam:g}) matches the event oracle")


def phase_taskq_sharded(clock, seed: int) -> None:
    from repro.fleet import frontier_points
    from repro.taskq import TaskqSweep

    with Phase(clock, f"sharded exact engine ({MESH_DEVICES} devices)") as ph:
        _, pools, cases = taskq_setup(seed)
        one = TaskqSweep(chunk=64)
        ref = one.run(cases, ARRIVALS, pools, stream=True)
        mesh = TaskqSweep(chunk=64, mesh=MESH_DEVICES)
        st = mesh.run(cases, ARRIVALS, pools, stream=True)
        a, b = ref.streamed.red, st.streamed.red
        check(sorted(a) == sorted(b), "streamed statistics differ in name")
        for name in a:
            check(np.array_equal(a[name], b[name], equal_nan=True),
                  f"streamed statistic {name!r}: sharded differs from one device")
        pa = [json.dumps(p.to_dict()) for p in frontier_points(ref)]
        pb = [json.dumps(p.to_dict()) for p in frontier_points(st)]
        check(pa == pb, "frontier points: sharded differs from one device")
        # Placement: the sharded launch's outputs must sit on every device.
        mat = mesh.run(cases, ARRIVALS, pools)
        total = mat.out["total"]
        shards = sorted((s.device.id, s.data.shape[0]) for s in total.addressable_shards)
        check(len({d for d, _ in shards}) == MESH_DEVICES
              and all(rows == len(cases) // MESH_DEVICES for _, rows in shards),
              f"sharded outputs are not split over {MESH_DEVICES} devices: {shards}")
        check(np.array_equal(np.asarray(total),
                             one.run(cases, ARRIVALS, pools).to_numpy()["total"]),
              "materialized sharded delays differ from one device")
        ph.sizes = (f"{len(cases)} cases x {ARRIVALS} arrivals streamed over a "
                    f"{MESH_DEVICES}-device "
                    f"grid mesh: {len(a)} statistics and {len(pa)} frontier points bit-exact "
                    f"vs one device; output shards (device, rows) {shards}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, MESH_DEVICES), default=1,
                    help="4: run only the mesh-sharded sweep and its one-device twin")
    args = ap.parse_args(argv)

    devs = device_gate(args.chips)
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = CompileClock()
    if args.chips == MESH_DEVICES:
        phase_taskq_sharded(clock, args.seed)
    else:
        phase_storage(clock, args.seed)
        params = phase_serving(clock, args.seed)
        phase_checkpoint(clock, args.seed, params)
        phase_taskq(clock, args.seed)
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {"platform": d0.platform,
                                             "kind": d0.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
