"""Compiles for a described TPU v5e: no chip needed, nothing runs.

The TPU compiler is installed with JAX, so the main path's kernels can be
compiled for a ``v5e:2x2`` topology that is described, not attached. That
catches what the Pallas interpreter cannot (Mosaic refusing a cast, a block
shape or a reshape) at no chip time. Covered here:

* the fused GF(2) codec kernel at the buckets of ``chip_smoke.py``'s
  phases — the 3 MiB storage class (k=6, strips 2^19 B), a lower-k decode
  with wider strips (the checkpoint's embedding leaf) and the serving
  phase's small prompt objects;
* the ``ClosedLoopServer`` fused admission → decode → prefill launch at
  qwen1.5-0.5b widths, compiled from shapes;
* the cached decode step of qwen1.5-0.5b at its benchmark cell's shapes
  (16 prompts of 1024 tokens, 16 tokens out), which must keep its K/V cache
  in place: no relayout copy of a layer's cache, the cache donated, stored
  without lane padding, and read in the layout the fused launch writes;
* that launch and the cached decode step of moonlight-16b-a3b at its
  benchmark cell's shapes (16 prompts of 1024 tokens, 16 of the 64 routed
  experts held, all 27 layers), which must fit one chip's 16 GB;
* the MoE expert contraction, which must keep bf16 operands with f32
  accumulation on the TPU whatever its CPU lowering does.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and xdist workers import every test
file), and JAX's persistent compile cache is off while these tests run — a
described-device compile is written to it but cannot be read back.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _on(sharding, tree):
    """Shapes of ``tree`` placed on ``sharding`` (arrays or shape structs)."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize(
    "batch,m,k,B",
    [
        (1, 8, 6, 2**19),   # storage write: (12, 6) parity rows, one 3 MiB object
        (32, 8, 6, 2**19),  # storage read round: k=6 decode, 32 objects
        (4, 2, 6, 2**19),   # adapted write: a shorter strip prefix
        (1, 4, 4, 2**27),   # checkpoint: (8, 4) decode of the 311 MB embedding
        (8, 4, 4, 512),     # serving prompts: 2 KiB objects, batch 8
    ],
)
def test_codec_kernel_compiles_for_v5e(one_chip, batch, m, k, B):
    from repro.kernels.gf2mm.gf2mm import gf2_rs_matmul_bytes

    mats = jax.ShapeDtypeStruct((batch, 8 * m, 8 * k), jnp.uint8, sharding=one_chip)
    data = jax.ShapeDtypeStruct((batch, k, B), jnp.uint8, sharding=one_chip)
    fn = jax.jit(lambda a, d: gf2_rs_matmul_bytes(a, d, interpret=False))
    compiled = fn.lower(mats, data).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.output_shardings.device_set == one_chip.device_set


def _closed_loop_launch(arch, params, batch, prompt_len, steps, sharding):
    """The fused launch of ``arch`` compiled for ``sharding``, and its engine."""
    from repro.coding.codec import get_codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core import PAPER_READ_3MB, RequestClass, TOFECPolicy
    from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
    from repro.storage import MemoryStore, Proxy

    engine = ServingEngine(arch, params, max_seq=prompt_len + steps)
    layout = SharedKeyLayout(K=4, r=2, strip_bytes=prompt_len)
    cls = RequestClass("prompt", layout.file_bytes / 2**20, PAPER_READ_3MB,
                       k_max=4, r_max=2.0, n_max=8)
    codec = get_codec("pallas", interpret=False)
    step = FusedServingStep.for_policy(ServePolicy.tofec(), cls, 16, codec=codec)
    proxy = Proxy(MemoryStore(), TOFECPolicy.for_classes([cls], L=16), L=16)
    try:
        srv = ClosedLoopServer(engine, proxy, layout, step, prompt_len=prompt_len)
        present = np.tile(np.arange(4, 8), (batch, 1))  # every item lost strips 0-3
        mats = codec.decode_mats(present, layout.N, layout.K)
        rows = np.zeros((batch, layout.K, layout.strip_bytes), np.uint8)
        mats_p, rows_p, bkey = codec.pad_to_bucket("dec", mats, rows, layout.N, layout.K)
        fn = srv._fn(("pfd", *bkey, prompt_len, layout.strip_bytes))
        args = (step.tables, step.carry, codec.backend.prep_mats(mats_p), rows_p,
                np.float32(batch), np.float32(-1.0), params)
        return fn.lower(*_on(sharding, args)).compile(), engine
    finally:
        proxy.close()


def _bytes_used(compiled) -> int:
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes


def test_closed_loop_launch_compiles_at_qwen_widths(one_chip):
    from repro.models.registry import get

    batch, prompt_len, steps = 8, 512, 16
    arch = get("qwen1.5-0.5b")
    params = jax.eval_shape(arch.init, jax.random.key(0))
    compiled, _ = _closed_loop_launch(arch, params, batch, prompt_len, steps, one_chip)
    assert "tpu_custom_call" in compiled.as_text()  # the codec kernel is fused in
    assert _bytes_used(compiled) < 16e9  # one v5e chip holds 16 GB
    logits = compiled.out_info[4]
    assert logits.shape == (batch, 1, arch.cfg.vocab)


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def _copies_of_at_least(compiled, elements: int) -> list[str]:
    """The optimized HLO's copies and transposes with a result of at least
    ``elements`` elements."""
    out = []
    for ln in compiled.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* (copy|transpose)\(", ln)
        if m and np.prod([int(d) for d in m.group(1).split(",") if d]) >= elements:
            out.append(ln.strip()[:200])
    return out


def test_qwen_decode_keeps_its_cache_in_place_on_v5e(one_chip):
    """qwen1.5-0.5b at its cell's shapes: each layer reads its K and V where
    the step writes them (no relayout of a layer's slice, none of the
    stack), the donated K/V are the step's output buffers, a cache row
    (16 kv heads of 64) fills whole 128-lane tiles, and the fused launch
    writes the cache in the layout the decode step reads."""
    from repro.models.registry import get
    from repro.serve.engine import split_cache

    batch, prompt_len, steps = 16, 1024, 16
    arch = get("qwen1.5-0.5b")
    cfg = arch.cfg
    params = jax.eval_shape(arch.init, jax.random.key(0))
    launch, engine = _closed_loop_launch(arch, params, batch, prompt_len, steps, one_chip)
    kv, rest = split_cache(jax.eval_shape(lambda: arch.init_cache(batch, prompt_len + steps)))
    token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    decode = engine._decode_step.lower(*_on(one_chip, (params, token, kv, rest))).compile()
    layer_slice = batch * (prompt_len + steps) * cfg.n_kv_heads * cfg.hd
    assert _copies_of_at_least(decode, layer_slice) == []
    mem = decode.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(kv) == 1_635_778_560
    cache_args = mem.argument_size_in_bytes - _nbytes((params, token, rest))
    assert cache_args <= 1.05 * _nbytes(kv)
    written = launch.output_formats[5]
    read = decode.input_formats[0][2]
    assert all(written[n] == read[n] == decode.output_formats[1][n] for n in kv)


def test_closed_loop_moe_launch_and_decode_fit_one_v5e(one_chip):
    """moonlight-16b-a3b at its cell's shapes: 5.16 B parameters (10.33 GB)
    on the chip, the latent cache, the grouped expert dispatch's buffers."""
    import dataclasses

    from repro.models import lm
    from repro.models.registry import Arch, get
    from repro.serve.engine import split_cache

    batch, prompt_len, steps = 16, 1024, 16
    cfg = dataclasses.replace(get("moonlight-16b-a3b").cfg, experts_held=16)
    arch = Arch(cfg=cfg, module=lm)
    params = jax.eval_shape(arch.init, jax.random.key(0))
    launch, engine = _closed_loop_launch(arch, params, batch, prompt_len, steps, one_chip)
    assert _bytes_used(launch) < 16e9  # one v5e chip holds 16 GB
    kv, rest = split_cache(jax.eval_shape(lambda: arch.init_cache(batch, prompt_len + steps)))
    assert launch.out_info[5]["ckv"].shape == (27, batch, prompt_len + steps, 512)
    assert launch.out_info[5]["expert_tokens"].shape == (26, 17)
    token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    decode = engine._decode_step.lower(*_on(one_chip, (params, token, kv, rest))).compile()
    assert _bytes_used(decode) < 16e9
    assert decode.memory_analysis().alias_size_in_bytes >= _nbytes(kv)  # the latent cache
    assert decode.out_info[0].shape == (batch, 1, cfg.vocab)


def test_moe_expert_contraction_stays_bf16_on_v5e(one_chip):
    from repro.models.moe import moe_mlp
    from repro.models.registry import get

    arch = get("mixtral-8x7b", smoke=True)
    params = jax.eval_shape(arch.init, jax.random.key(0))
    moe = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                       params["layers"]["moe"])  # one layer of the stack
    x = jax.ShapeDtypeStruct((2, 16, arch.cfg.d_model), jnp.bfloat16)
    fn = jax.jit(lambda p, x: moe_mlp(p, arch.cfg, x)[0])
    text = fn.lower(*_on(one_chip, (moe, x))).as_text()
    experts = [ln for ln in text.splitlines()
               if "stablehlo.dot_general" in ln and "batching_dims" in ln]
    assert len(experts) == 3  # wi, wg, wo
    for ln in experts:
        operands, result = ln.rsplit("->", 1)
        assert operands.count("bf16>") == 2 and "xf32>" in result, ln


def test_codec_kernel_keeps_its_names_for_v5e(one_chip):
    """The device trace finds the codec by its custom call (``tpu_custom_call``),
    now named after the kernel, inside the codec's named jitted function."""
    from repro.coding.codec import get_codec
    from repro.kernels.gf2mm.gf2mm import KERNEL_NAME

    fn = get_codec("pallas", interpret=False).backend._build(6)
    mats = jax.ShapeDtypeStruct((2, 64, 48), jnp.uint8, sharding=one_chip)
    data = jax.ShapeDtypeStruct((2, 6, 2**19), jnp.uint8, sharding=one_chip)
    lowered = fn.lower(mats, data)
    assert lowered.as_text().startswith("module @jit_rs_codec_matmul ")
    assert f'kernel_name = "{KERNEL_NAME}"' in lowered.as_text()
    calls = [ln for ln in lowered.compile().as_text().splitlines()
             if "custom-call(" in ln]
    assert len(calls) == 1
    assert f"%{KERNEL_NAME}" in calls[0] and 'custom_call_target="tpu_custom_call"' in calls[0]
