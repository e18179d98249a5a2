"""Serving demo: a language model fed from erasure-coded prompt storage.

Prompts live in the emulated store as Shared-Key coded objects behind an
S3-like latency model. A ClosedLoopServer serves them in rounds: the proxy
fetches each round's prompts with adaptive (n, k) ranged reads, and one
jitted step covers the admission update → batched MDS decode →
bytes→tokens → LM prefill, before greedy decoding. The controller's (n, k)
pick is fed back into the proxy's write policy, so queued writes encode
under the adapted code.

The fused step runs the default codec when it is jitted
(``REPRO_CODEC_BACKEND=jnp`` or ``pallas``, or the Pallas kernel a TPU
defaults to) and the Pallas codec otherwise: the compiled kernel on a TPU,
the interpreter on the CPU.

Run:  PYTHONPATH=src python examples/serve_demo.py [--fast]
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
from repro.storage import LatencyStore, MemoryStore, Proxy
from repro.storage.proxy import store_coded_object

CFG = dataclasses.replace(
    QWEN, name="serve-demo", n_layers=4, d_model=256, n_heads=8, n_kv_heads=8,
    d_ff=512, vocab=4096,
)


def run(fast: bool):
    arch = Arch(cfg=CFG, module=_FAMILY_MODULES["dense"])
    eng = ServingEngine(arch, arch.init(jax.random.key(0)), max_seq=96)

    prompt_len = 32
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    inner = MemoryStore()
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
    steps = 4 if fast else 8
    rounds = 2 if fast else 4
    write_pol = FeedbackPolicy(layout.N, layout.K)
    proxy = Proxy(LatencyStore(inner, PAPER_READ_3MB, time_scale=1e-3, seed=2),
                  TOFECPolicy.for_classes([cls], L=8), L=8, write_policy=write_pol)
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
            print("  generated token grid (batch × steps):")
            print(res.tokens)
            for key, code, d in zip(res.served_keys, res.codes, res.storage_total_s):
                print(f"  {key}: read ({code[0]},{code[1]})  {d * 1e3:.1f} ms wall")
            # queue a write: it encodes under the fed-back code at the next
            # admission round — the write path follows the controller.
            payload = rng.integers(0, 256, layout.file_bytes,
                                   dtype=np.uint8).tobytes()
            proxy.write_async(f"out/{rnd}", layout, payload)
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
    ap.add_argument("--fast", action="store_true",
                    help="fewer prompts, rounds and steps for CI smoke runs")
    run(ap.parse_args().fast)


if __name__ == "__main__":
    main()
