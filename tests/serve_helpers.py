"""Set-up shared by the serving tests: prompts stored as coded objects, a
closed-loop server over them, and the plain greedy reference its tokens are
checked against."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.coding.codec import Codec
from repro.coding.layout import SharedKeyLayout
from repro.core import PAPER_READ_3MB, RequestClass, StaticPolicy
from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
from repro.storage import MemoryStore, Proxy
from repro.storage.proxy import store_coded_object


def store_prompts(store, layout, prompts, prefix="p") -> list[str]:
    """Store each (prompt_len,) row of ``prompts`` as a coded object."""
    keys = []
    for i, toks in enumerate(prompts):
        keys.append(f"{prefix}/{i}")
        store_coded_object(store, keys[-1], layout, np.asarray(toks, np.int32).tobytes())
    return keys


def greedy_reference(arch, params, prompts, steps: int, *, max_seq: int) -> np.ndarray:
    """(B, steps) greedy tokens of an undonated ``arch.prefill`` +
    ``arch.decode_step`` loop over ``prompts``."""
    logits, cache = jax.jit(lambda p, b: arch.prefill(p, b, max_seq=max_seq))(
        params, {"tokens": jnp.asarray(prompts, jnp.int32)})
    decode = jax.jit(arch.decode_step)
    out = []
    for _ in range(steps):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, tok, cache)
    return np.stack(out, axis=1)


def prompt_class(layout) -> RequestClass:
    return RequestClass("prompt", layout.file_bytes / 2**20, PAPER_READ_3MB,
                        k_max=layout.K, r_max=float(layout.r), n_max=layout.N)


@contextlib.contextmanager
def closed_loop(arch, params, prompts, *, max_seq: int, store=None, read_policy=None,
                write_policy=None, serve_policy=None, request_class=None, L: int = 16,
                codec: str = "jnp"):
    """Yields (server, keys) over ``prompts`` stored in a (K=4, r=2) layout
    whose strips hold one prompt; ``store`` wraps the inner MemoryStore
    (fault or latency injection). The proxy closes on exit."""
    prompts = np.asarray(prompts, np.int32)
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompts.shape[1])
    inner = MemoryStore()
    keys = store_prompts(inner, layout, prompts)
    proxy = Proxy(store(inner) if store else inner, read_policy or StaticPolicy(8, 4),
                  L=8, write_policy=write_policy)
    step = FusedServingStep.for_policy(serve_policy or ServePolicy.tofec(),
                                       request_class or prompt_class(layout), L,
                                       codec=Codec(codec))
    server = ClosedLoopServer(ServingEngine(arch, params, max_seq=max_seq), proxy,
                              layout, step, prompt_len=prompts.shape[1])
    try:
        yield server, keys
    finally:
        proxy.close()
