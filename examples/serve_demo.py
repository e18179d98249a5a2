"""Serving demo: batched decode with erasure-coded prompt storage.

Prompts live in the emulated store as Shared-Key coded objects; the proxy
fetches them with adaptive (n, k) ranged reads under an S3-like latency
model, tolerating injected read failures; the LM then prefills + decodes.

The fetch runs twice: once on the unfused path (the proxy's decoder thread
decodes completions on the host codec) and once through the
fused serving step — one jitted launch running the TOFEC admission update
AND the batched MDS decode for the whole round. The fused step runs the
default codec when it is jitted (``REPRO_CODEC_BACKEND=jnp`` or ``pallas``,
or the Pallas kernel a TPU defaults to) and the Pallas codec otherwise: the
compiled kernel on a TPU, the interpreter on the CPU.

``--closed-loop`` runs the full serving tower instead: a ClosedLoopServer
whose single jitted step covers admission update → batched decode →
bytes→tokens → LM prefill, with the controller's (n, k) pick fed back into
the proxy's write policy so queued writes re-encode under the adapted code.

Run:  PYTHONPATH=src python examples/serve_demo.py [--closed-loop] [--fast]
"""

import argparse
import dataclasses

import jax
import numpy as np

from repro.coding.codec import get_codec
from repro.coding.layout import SharedKeyLayout
from repro.configs.qwen1_5_0_5b import CONFIG as QWEN
from repro.core import (
    PAPER_READ_3MB,
    FeedbackPolicy,
    RequestClass,
    TOFECPolicy,
)
from repro.models.registry import Arch, _FAMILY_MODULES
from repro.serve import (
    ClosedLoopServer,
    FusedServingStep,
    ServePolicy,
    ServingEngine,
)
from repro.storage import FaultyStore, LatencyStore, MemoryStore, Proxy
from repro.storage.proxy import store_coded_object

CFG = dataclasses.replace(
    QWEN, name="serve-demo", n_layers=4, d_model=256, n_heads=8, n_kv_heads=8,
    d_ff=512, vocab=4096,
)


def _setup(fast: bool, p_fail: float = 0.15):
    arch = Arch(cfg=CFG, module=_FAMILY_MODULES["dense"])
    params = arch.init(jax.random.key(0))
    eng = ServingEngine(arch, params, max_seq=96)

    prompt_len = 32
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    inner = MemoryStore()
    store = FaultyStore(
        LatencyStore(inner, PAPER_READ_3MB, time_scale=1e-3, seed=2),
        p_fail=p_fail, seed=3,
    )
    rng = np.random.default_rng(1)
    keys = []
    for i in range(4 if fast else 6):
        toks = rng.integers(0, CFG.vocab, size=(prompt_len,)).astype(np.int32)
        store_coded_object(inner, f"prompt/{i}", layout, toks.tobytes())
        keys.append(f"prompt/{i}")

    cls = RequestClass("prompt", prompt_len * 4 / 2**20, PAPER_READ_3MB,
                       k_max=4, r_max=2.0, n_max=8)
    codec = get_codec()
    if not codec.backend.jitted:  # the host numpy oracle cannot run fused
        codec = get_codec("pallas")
    return eng, layout, inner, store, keys, cls, codec, prompt_len, rng


def run_fused_fetch(fast: bool):
    eng, layout, _, store, keys, cls, codec, prompt_len, _ = _setup(fast)
    steps = 4 if fast else 8
    fused = FusedServingStep.for_class(cls, L=8, codec=codec)
    proxy = Proxy(store, TOFECPolicy.for_classes([cls], L=8), L=8)
    try:
        res = eng.serve(proxy, layout, keys, prompt_len=prompt_len, steps=steps)
        print("generated token grid (batch × steps):")
        print(res.tokens)
        print("\nper-prompt storage fetch: code (n,k), delay")
        for key, code, d in zip(keys, res.codes, res.storage_total_s):
            print(f"  {key}: ({code[0]},{code[1]})  {d * 1e3:.1f} ms wall")
        print(f"\n15% injected read-failure rate absorbed by erasure coding; "
              f"{sum(r.failures for r in proxy.results)} task failures total")

        fres = eng.serve(proxy, layout, keys, prompt_len=prompt_len, steps=steps,
                         fused=fused)
        match = np.array_equal(fres.tokens, res.tokens)
        print(f"\nfused serving step ({codec.name} backend): one jitted launch "
              f"ran the TOFEC admission update + batched decode of all "
              f"{len(keys)} prompts")
        print(f"  tokens match unfused path: {match}")
        print(f"  controller pick for the next round: (n,k)={fres.next_code}, "
              f"compiled traces so far: {fused.traces}")
    finally:
        proxy.close()


def run_closed_loop(fast: bool):
    # Writes must land durably for the round-trip, so no injected failures
    # on this path (reads would shrug them off; the demo writes too).
    eng, layout, inner, _, keys, cls, codec, prompt_len, rng = _setup(
        fast, p_fail=0.0)
    store = LatencyStore(inner, PAPER_READ_3MB, time_scale=1e-3, seed=2)
    steps = 4 if fast else 8
    rounds = 2 if fast else 4
    write_pol = FeedbackPolicy(layout.N, layout.K)
    proxy = Proxy(store, TOFECPolicy.for_classes([cls], L=8), L=8,
                  write_policy=write_pol)
    step = FusedServingStep.for_policy(ServePolicy.tofec(), cls, 8, codec=codec)
    srv = ClosedLoopServer(eng, proxy, layout, step, prompt_len=prompt_len)
    try:
        print(f"closed-loop serving tower ({codec.name} backend): one jitted "
              f"step per round = admission update → batched decode → "
              f"bytes→tokens → LM prefill")
        for rnd in range(rounds):
            res = srv.serve_round(keys, steps=steps)
            print(f"\nround {rnd}: served {len(res.served_keys)}/{len(keys)} "
                  f"prompts, controller pick (n,k)={res.next_code} "
                  f"(pushed to write policy: {write_pol.code})")
            # queue a write: it encodes under the fed-back code at the next
            # admission round — the write path follows the controller.
            payload = rng.integers(0, 256, layout.file_bytes,
                                   dtype=np.uint8).tobytes()
            srv.put(f"out/{rnd}", payload)
        proxy.flush_writes()
        wres = [r for r in proxy.results if r.op == "write"]
        print(f"\n{len(wres)} writes flushed; codes used: "
              f"{sorted({(r.n, r.k) for r in wres})}")
        back = proxy.read(f"out/{rounds - 1}", layout,
                          payload_len=layout.file_bytes)
        print(f"read-back of last write under adapted code: ok={back.ok}")
        print(f"compiled closed-loop traces: {srv.traces} "
              f"(bounded per shape bucket)")
    finally:
        proxy.close()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--closed-loop", action="store_true",
                    help="run the closed-loop serving tower (fused admission "
                         "+ decode + prefill, write policy fed back)")
    ap.add_argument("--fast", action="store_true",
                    help="smaller batch/steps for CI smoke runs")
    args = ap.parse_args()
    if args.closed_loop:
        run_closed_loop(args.fast)
    else:
        run_fused_fetch(args.fast)


if __name__ == "__main__":
    main()
