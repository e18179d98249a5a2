"""Whole runs with the timed path broken underneath: ``correct`` must come out
false for every fault a cell can have, and true with nothing broken.

Each run skips the harness's look for a chip (see ``bench_tiny``) and is
otherwise the run the benchmark makes, at a size the CPU holds.
"""

import bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def fresh_jit_caches():
    jax.clear_caches()  # a patched function must be traced again
    yield
    jax.clear_caches()


def _flip_first_byte(blobs):
    out = []
    for b in blobs:
        b = bytearray(b)
        b[0] ^= 0xFF
        out.append(bytes(b))
    return out


@pytest.mark.parametrize("workload,traffic", [(bench_tiny.READ, None),
                                              (bench_tiny.READ, bench_tiny.WRITE_MIX),
                                              (bench_tiny.PROMPT, None)],
                         ids=["read", "write", "prompt"])
def test_sound_run_is_correct(workload, traffic):
    result, _ = bench_tiny.run_tiny(workload, traffic=traffic)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


def _read_answer_altered(monkeypatch):
    from repro.coding.layout import SharedKeyLayout

    orig = SharedKeyLayout.reconstruct_batch
    monkeypatch.setattr(SharedKeyLayout, "reconstruct_batch",
                        lambda self, items, codec=None: _flip_first_byte(orig(self, items, codec)))


def _read_half_batch(monkeypatch):
    """The batched decode answers for the first half of its items only."""
    from repro.coding.layout import SharedKeyLayout

    orig = SharedKeyLayout.reconstruct_batch

    def half(self, items, codec=None):
        out = orig(self, items, codec)
        keep = len(out) // 2
        return out[:keep] + [bytes(len(b)) for b in out[keep:]]

    monkeypatch.setattr(SharedKeyLayout, "reconstruct_batch", half)


def _write_answer_altered(monkeypatch):
    from repro.coding.layout import SharedKeyLayout

    orig = SharedKeyLayout.encode_files
    monkeypatch.setattr(SharedKeyLayout, "encode_files",
                        lambda self, payloads, codec=None, **kw:
                        _flip_first_byte(orig(self, payloads, codec, **kw)))


def _write_state_unchanged(monkeypatch):
    """Acknowledged writes never reach the stored object."""
    from repro.storage.proxy import Proxy

    monkeypatch.setattr(Proxy, "_finalize_write_inner", lambda self, req: req.settled.set())


def _patch_decode(monkeypatch, change):
    from repro.models import lm

    orig = lm.decode_step

    def broken(params, cfg, token, cache):
        logits, new_cache = orig(params, cfg, token, cache)
        return change(logits, cache, new_cache)

    monkeypatch.setattr(lm, "decode_step", broken)


def _serve_state_unchanged(monkeypatch):
    """The decode step hands back the cache it was given."""
    _patch_decode(monkeypatch, lambda logits, cache, new: (logits, cache))


def _serve_half_batch(monkeypatch):
    """The decode step leaves the second half of the batch out."""
    def half(logits, cache, new):
        keep = logits.shape[0] // 2
        return logits.at[keep:].set(0.0), new

    _patch_decode(monkeypatch, half)


def _serve_token_altered(monkeypatch):
    """Every decode step's pick moves to the next token id."""
    def shift(logits, cache, new):
        return jnp.roll(logits, 1, axis=-1), new

    _patch_decode(monkeypatch, shift)


def _serve_prompt_altered(monkeypatch):
    """The fused launch's decoded prompt loses its first token."""
    from repro.serve import engine

    orig = engine.tokens_from_strips
    monkeypatch.setattr(engine, "tokens_from_strips",
                        lambda *a: orig(*a).at[:, 0].add(1))


# serving: enough decode steps that a stale cache shows in the tokens
SERVE_MIX = {"output_len": 16}
FAULTS = [
    (bench_tiny.READ, None, _read_answer_altered, "wrong_payloads"),
    (bench_tiny.READ, None, _read_half_batch, "wrong_payloads"),
    (bench_tiny.READ, bench_tiny.WRITE_MIX, _write_answer_altered, "unreadable_after_flush"),
    (bench_tiny.READ, bench_tiny.WRITE_MIX, _write_state_unchanged, "stored_strips_wrong"),
    (bench_tiny.PROMPT, SERVE_MIX, _serve_state_unchanged, "token_gap"),
    (bench_tiny.PROMPT, SERVE_MIX, _serve_half_batch, "token_gap"),
    (bench_tiny.PROMPT, SERVE_MIX, _serve_token_altered, "token_gap"),
    (bench_tiny.PROMPT, SERVE_MIX, _serve_prompt_altered, "prompt_tokens_wrong"),
]


@pytest.mark.parametrize("workload,traffic,fault,check", FAULTS,
                         ids=[f.__name__.strip("_") for _, _, f, _ in FAULTS])
def test_fault_makes_run_incorrect(monkeypatch, workload, traffic, fault, check):
    fault(monkeypatch)
    result, _ = bench_tiny.run_tiny(workload, traffic=traffic)
    assert not result["correct"]
    c = result["checks"][check]
    assert c["value"] > c["limit"], result["checks"]


def test_control_reads_wider_gap_than_the_program():
    """The serving control (fp8 weights, bf16 activations) at a CPU size:
    4 layers, 4096 ids, 16 requests of 32 served tokens."""
    cfg, tr = bench_tiny.tiny(bench_tiny.PROMPT)
    cfg.update(vocab_size=4096, initializer_range=0.1, num_hidden_layers=4)
    cfg["prompt_store"]["prompts"] = 32
    tr.update(output_len=32, check_requests=16, prompt_len=32, clients=16)
    from repro.coding.codec import get_codec

    result, rec = bench_tiny.bench_run.execute(
        bench_tiny.READ, 5, 1.0, False, require_chip=False, cache=False,
        codec=get_codec("jnp"), config=cfg, traffic=tr, control=True)
    program = result["checks"]["token_gap"]["value"]
    assert np.isfinite(program) and rec.control["token_gap"] > 3 * program
