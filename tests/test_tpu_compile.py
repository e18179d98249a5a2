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
* the MoE expert contraction, which must keep bf16 operands with f32
  accumulation on the TPU whatever its CPU lowering does.

The topology is described inside a module fixture (never at import: only
one process may load the TPU library, and xdist workers import every test
file), and JAX's persistent compile cache is off while these tests run — a
described-device compile is written to it but cannot be read back.
"""

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


def test_closed_loop_launch_compiles_at_qwen_widths(one_chip):
    from repro.coding.codec import get_codec
    from repro.coding.layout import SharedKeyLayout
    from repro.core import PAPER_READ_3MB, RequestClass, TOFECPolicy
    from repro.models.registry import get
    from repro.serve import ClosedLoopServer, FusedServingStep, ServePolicy, ServingEngine
    from repro.storage import MemoryStore, Proxy

    batch, prompt_len, steps = 8, 512, 16
    arch = get("qwen1.5-0.5b")
    params = jax.eval_shape(arch.init, jax.random.key(0))
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
        fn = srv._fn(("pfd", *bkey, prompt_len, layout.strip_bytes, False))
        args = (step.tables, step.carry, codec.backend.prep_mats(mats_p), rows_p,
                np.float32(batch), np.float32(-1.0), params)
        compiled = fn.lower(*_on(one_chip, args)).compile()
    finally:
        proxy.close()
    assert "tpu_custom_call" in compiled.as_text()  # the codec kernel is fused in
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16e9, used  # one v5e chip holds 16 GB
    logits = compiled.out_info[4]
    assert logits.shape == (batch, 1, arch.cfg.vocab)


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
