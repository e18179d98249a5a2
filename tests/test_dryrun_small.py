"""Dry-run machinery validation at test scale (8 host devices, subprocess).

Covers: the XLA while-loop-counted-once fact the FLOPs pass corrects for,
the collective-bytes HLO parser, and a miniature end-to-end dry-run cell
(sharded lower + compile + roofline) on a 2×4 mesh with a smoke config.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=540, env=env, cwd=ROOT,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_scan_flops_counted_once_and_unroll_corrects():
    out = _run_py("""
        import jax, jax.numpy as jnp
        def body(c, _): return c @ c, None
        def f(unroll):
            def g(x):
                y, _ = jax.lax.scan(body, x, None, length=7, unroll=unroll)
                return y
            return g
        x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        rolled = jax.jit(f(False)).lower(x).compile().cost_analysis()["flops"]
        unrolled = jax.jit(f(True)).lower(x).cost_analysis()["flops"]
        print(f"RATIO {unrolled / rolled}")
    """)
    ratio = float(out.split("RATIO ")[1])
    assert ratio == pytest.approx(7.0, rel=0.05)


def test_collective_parser_on_real_partitioned_hlo():
    out = _run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.roofline import collective_bytes
        mesh = jax.make_mesh((8,), ("d",))
        with mesh:
            def g(a, b):
                return jnp.sum(a @ b)
            gs = jax.jit(g,
                in_shardings=(NamedSharding(mesh, P(None, "d")), NamedSharding(mesh, P("d", None))),
                out_shardings=NamedSharding(mesh, P()))
            a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
            comp = gs.lower(a, a).compile()
        cb = collective_bytes(comp.as_text())
        print("COLL", cb["all-reduce"], cb["count"])
    """)
    _, ar_bytes, count = out.strip().rsplit(" ", 2)[-3:], None, None
    parts = out.strip().split()
    ar_bytes, count = int(parts[-2]), int(parts[-1])
    assert count >= 1
    # contraction-sharded matmul all-reduces the (256, 256) f32 result.
    assert ar_bytes >= 256 * 256 * 4


def test_mini_dryrun_cell_sharded_compile_and_roofline():
    out = _run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.registry import get
        from repro.models.sharding import axis_rules, spec_for
        from repro.launch.mesh import make_auto_mesh
        from repro.launch.roofline import analyze
        from repro.launch.specs import _specs_tree, _batch_shardings, batch_specs
        from repro.train.train_step import make_train_step
        from repro.train.optimizer import init_opt_state
        from repro.models.config import ShapeSpec

        arch = get("qwen1.5-0.5b", smoke=True)
        shape = ShapeSpec("mini", "train", seq=64, batch=8)
        mesh = make_auto_mesh((2, 4), ("data", "model"))
        with mesh:
            with axis_rules(mesh):
                params = jax.eval_shape(lambda: arch.init(jax.random.key(0)))
                p_specs = _specs_tree(mesh, params, arch.logical_axes())
                opt = jax.eval_shape(lambda: init_opt_state(params))
                o_specs = {"m": p_specs, "v": p_specs, "step": NamedSharding(mesh, P())}
                batch = batch_specs(arch.cfg, shape, "train")
                b_specs = _batch_shardings(mesh, arch.cfg, batch)
                fn = make_train_step(arch)
                jfn = jax.jit(fn, in_shardings=(p_specs, o_specs, b_specs),
                              out_shardings=(p_specs, o_specs, None))
                compiled = jfn.lower(params, opt, batch).compile()
                roof = analyze(compiled, 8)
        mem = compiled.memory_analysis()
        print("RESULT", roof.flops > 0, roof.hbm_bytes > 0,
              mem.temp_size_in_bytes >= 0, roof.dominant)
    """)
    assert "RESULT True True True" in out


def test_mini_dryrun_decode_cells_sharded_compile():
    """The decode specs of ``launch.specs`` (cache shardings from each
    module's ``cache_logical_axes``, the cache donated) compile on a 2×4
    mesh: batch × kv-heads, the long-context B = 1 sequence split, the
    batch × sequence lever, gemma2's local/global layers, the hybrid's and
    the encoder-decoder's caches."""
    out = _run_py("""
        import dataclasses
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding
        from repro.models.registry import Arch, get
        from repro.models.sharding import axis_rules, spec_for
        from repro.launch.mesh import make_auto_mesh
        from repro.launch.specs import _cache_shardings, _specs_tree

        mesh = make_auto_mesh((2, 4), ("data", "model"))
        for name, B, seq_shard in [("qwen1.5-0.5b", 8, False), ("qwen1.5-0.5b", 1, False),
                                   ("mixtral-8x7b", 8, True), ("gemma2-2b", 4, False),
                                   ("zamba2-2.7b", 8, False), ("whisper-base", 8, False)]:
            arch = get(name, smoke=True)
            cfg = dataclasses.replace(arch.cfg, decode_cache_seq_shard=seq_shard)
            arch = Arch(cfg=cfg, module=arch.module)
            with mesh, axis_rules(mesh):
                params = jax.eval_shape(lambda: arch.init(jax.random.key(0)))
                cache = jax.eval_shape(lambda: arch.init_cache(B, 64))
                c_specs = _cache_shardings(mesh, arch, B, cache)
                jfn = jax.jit(
                    arch.decode_step,
                    in_shardings=(_specs_tree(mesh, params, arch.logical_axes()),
                                  NamedSharding(mesh, spec_for((B, 1), ("batch", None))),
                                  c_specs),
                    out_shardings=(None, c_specs), donate_argnums=(2,))
                jfn.lower(params, jax.ShapeDtypeStruct((B, 1), jnp.int32), cache).compile()
            print("CELL", name, B, c_specs["k"].spec)
    """)
    cells = [ln for ln in out.splitlines() if ln.startswith("CELL")]
    assert len(cells) == 6, out
    assert "CELL qwen1.5-0.5b 1 PartitionSpec(None, None, 'model', None)" in cells
    assert "CELL mixtral-8x7b 8 PartitionSpec(None, 'data', 'model', None)" in cells


def test_dryrun_results_schema():
    """Any artifacts already produced by the sweep have the right schema."""
    d = os.path.join(ROOT, "benchmarks", "results", "dryrun")
    if not os.path.isdir(d) or not os.listdir(d):
        pytest.skip("no dry-run artifacts yet")
    for name in sorted(os.listdir(d))[:10]:
        try:
            with open(os.path.join(d, name)) as f:
                rec = json.load(f)
        except json.JSONDecodeError:
            continue  # sweep may be mid-write
        assert rec["status"] in ("ok", "skipped", "error"), name
        if rec["status"] == "ok":
            r = rec["roofline"]
            assert r["flops"] > 0 and r["chips"] in (256, 512)
            assert rec["useful_flops_ratio"] is None or rec["useful_flops_ratio"] < 1.5
