"""Driver for model serving fed from coded storage: a closed loop of rounds
through ``repro.serve.ClosedLoopServer``.

Set-up makes the weights on the device from the seed (``dense_lm``), stores
a pool of prompts from the seed as coded objects in one batched encode, and
serves one short warm-up round (two tokens), which compiles or loads every
program a round runs: the fused admission -> MDS decode -> prefill launch at
the round's batch bucket, and the cached decode step.

Each round sends one request per client, all at once (``serve_round``):
the prompts are fetched through the TOFEC proxy as raw chunks, decoded and
prefilled in one launch, then decoded greedily token by token. Rounds follow
each other until the window closes. Every generated token's host readback
is timed where the program asks for the next decode step. A traced run
profiles the window's last ``trace_seconds``, through the end of the round
that is under way at the close.

The check, after the window: every served request's decoded prompt equals
the stored tokens, and for a sample of requests drawn from the seed, every
served token lies within ``limits.token_gap`` of the float32 reference's
best logit at its position (prompt plus the tokens served before it).
"""

from __future__ import annotations

import time

import numpy as np

from benchlib import dense_lm
from benchlib.harness import Check, Phases, Round, RunRecord, Span, memory_peak_bytes

MIB = 2**20


def run(ctx) -> RunRecord:
    import jax

    from repro.coding.codec import get_codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core.controller import TOFECPolicy
    from repro.core.delay_model import DelayParams, RequestClass
    from repro.models import lm
    from repro.models.registry import Arch
    from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
    from repro.storage.backend import LatencyStore, MemoryStore
    from repro.storage.proxy import Proxy

    phases = Phases(ctx.t_proc0)
    phases.mark("start")
    cfg, tr = ctx.config, ctx.traffic
    st = cfg["prompt_store"]
    rec = RunRecord()
    rng = np.random.default_rng(ctx.seed)
    sizes = dense_lm.sizes(cfg)
    arch = Arch(cfg=dense_lm.program_config(cfg), module=lm)
    clients, plen, steps = int(tr["clients"]), int(tr["prompt_len"]), int(tr["output_len"])
    rec.model = sizes

    # -- set-up ---------------------------------------------------------------
    params = dense_lm.init_params(int(rng.integers(2**31)), sizes)
    jax.block_until_ready(params)
    phases.mark("weights")
    engine = ServingEngine(arch, params, max_seq=plen + steps)
    layout = SharedKeyLayout(K=int(st["K"]), r=int(st["r"]),
                             strip_bytes=plen * int(st["token_bytes"]) // int(st["K"]))
    n_prompts = int(st["prompts"])
    prompts = rng.integers(0, sizes["vocab"], (n_prompts, plen), dtype=np.int32)
    codec = ctx.codec or get_codec()
    inner = MemoryStore()
    coded = layout.encode_files([p.tobytes() for p in prompts], codec=codec)
    for i, blob in enumerate(coded):
        inner.put(f"prompt/{i}", blob)
    del coded
    phases.mark("prompts")
    read_p = DelayParams(**st["read_delay"])
    cls = RequestClass("prompt", layout.file_bytes / MIB, read_p, k_max=layout.K,
                       r_max=float(layout.r), n_max=layout.N)
    L = int(st["L"])
    spans = rec.spans

    class SpannedProxy(Proxy):
        def read_many(self, *a, **kw):
            with Span("serve.fetch", spans):
                return super().read_many(*a, **kw)

    proxy = SpannedProxy(
        LatencyStore(inner, read_p, time_scale=float(st["time_scale"]),
                     seed=int(rng.integers(2**62))),
        TOFECPolicy.for_classes([cls], L=L), L=L)
    step = FusedServingStep.for_policy(ServePolicy.tofec(), cls, L, codec=codec)
    srv = ClosedLoopServer(engine, proxy, layout, step, prompt_len=plen)
    readbacks: list[float] = []
    decode = engine._decode
    trace_s = float(tr["trace_seconds"])

    def timed_decode(p, tok, cache):
        # called right after the previous token's readback on the host
        readbacks.append(time.monotonic())
        with Span("serve.decode_call"):
            return decode(p, tok, cache)

    engine._decode = timed_decode

    def one_round(keys, n_steps=steps):
        readbacks.clear()
        t_send = time.monotonic()
        with Span("serve.round"):
            res = srv.serve_round([f"prompt/{k}" for k in keys], steps=n_steps)
        return Round(send=t_send, readbacks=list(readbacks), served=len(res.served_keys),
                     requested=len(keys)), res

    try:
        # warm-up: the decode step's program does not depend on the step count
        one_round(rng.choice(n_prompts, clients, replace=False), min(steps, 2))
        jax.effects_barrier()
        phases.mark("warm-up round")
        phases.print()

        # -- the window --------------------------------------------------------
        compiles0 = ctx.compiles.count if ctx.compiles else 0
        served: list[tuple] = []  # (key indices, tokens, decoded prompts) per round
        rec.t0 = time.monotonic()
        rec.setup_s = rec.t0 - ctx.t_proc0
        rec.t_end = rec.t0 + ctx.seconds
        while time.monotonic() < rec.t_end:
            if ctx.trace and rec.profiler is None and time.monotonic() >= rec.t_end - trace_s:
                from benchlib.harness import Profiler

                # The window's last trace_seconds, from a round's start to the
                # last round's end: stopping the profiler and reading its trace
                # take seconds, which must not stall a round inside the window.
                rec.profiler = Profiler()
                rec.profiler.start()
            keys = rng.choice(n_prompts, clients, replace=False)
            t_launch = time.monotonic()
            rd, res = one_round(keys)
            rec.rounds.append(rd)
            rec.launches.append((t_launch, rd.served, plen))
            idx = {f"prompt/{k}": int(k) for k in keys}
            served.append(([idx[k] for k in res.served_keys], res.tokens, res.prompts))
        if rec.profiler is not None:
            rec.profiler.stop()
        rec.compiles_in_window = (ctx.compiles.count if ctx.compiles else 0) - compiles0
        rec.memory_peak_bytes = memory_peak_bytes(ctx.devices or jax.devices()[:1])
    finally:
        proxy.close()
    rec.attempted = sum(rd.requested for rd in rec.rounds)
    rec.failed = sum(rd.requested - rd.served for rd in rec.rounds)
    # The program's state goes before the reference runs: only the weights stay.
    del srv, step, engine

    # -- the check ------------------------------------------------------------
    wrong_prompts = 0
    for kidx, _, dev_prompts in served:
        got = np.asarray(dev_prompts)[: len(kidx)]
        wrong_prompts += int((got != prompts[kidx]).any(axis=1).sum())
    rec.checks.append(Check("prompt_tokens_wrong", wrong_prompts, 0))

    # The sample, drawn from the seed, takes one request from each of
    # check_requests bands of batch rows, so every part of the batch is seen.
    n_check = min(int(tr["check_requests"]), clients)
    pick = []
    for band in np.array_split(np.arange(clients), n_check):
        row = int(rng.choice(band))
        rounds = [i for i, (kidx, _, _) in enumerate(served) if len(kidx) > row]
        if rounds:
            pick.append((int(rng.choice(rounds)), row))
    n_check = len(pick)
    seqs = np.stack([np.concatenate([prompts[served[i][0][r]], served[i][1][r][:-1]])
                     for i, r in pick])
    chosen = np.stack([served[i][1][r] for i, r in pick])
    positions = np.arange(plen - 1, plen - 1 + steps)
    block = max(1, min(n_check, 4))
    while n_check % block:
        block -= 1
    ref = dense_lm.reference_logits(params, sizes, seqs, positions, block=block)
    gap = dense_lm.widest_gap(ref, chosen)
    rec.checks.append(Check("token_gap", gap, float(cfg["limits"]["token_gap"])))
    if ctx.control:
        low = dense_lm.reference_logits(params, sizes, seqs, positions, control=True,
                                        block=block)
        rec.control["token_gap"] = dense_lm.widest_gap(ref, low.argmax(-1))
    return rec
