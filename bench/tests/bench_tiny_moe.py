"""A small stand-in for the latent-attention MoE serve cell, for CPU tests:
``bench_tiny``'s serve stand-in with the moonlight configuration cut to a
width a CPU holds."""

from __future__ import annotations

import copy
import time

import bench_tiny

MOE = "moonlight-16b-a3b.prompt-1k"


def tiny() -> dict:
    """The moonlight configuration at a size a CPU test holds."""
    cfg = bench_tiny.manifest.load_json(bench_tiny.BENCH / "configs" / "moonlight-16b-a3b.json")
    # 4 of 8 routed experts held, from the third: a share that starts past
    # expert 0; std 0.1 at width 64 as for the dense stand-in
    cfg.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
               num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
               kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               vocab_size=512, n_routed_experts=4, n_routed_experts_published=8,
               first_held_expert=2, num_experts_per_tok=2, initializer_range=0.1)
    cfg["prompt_store"].update(prompts=16, time_scale=0.02)
    # every position checked: no routing margin leaves one out
    cfg["limits"] = {"token_gap": 0.1, "route_margin": 0.0}
    return cfg


def run_tiny(seed: int = 3, seconds: float = 1.0, *, trace: bool = False):
    """(result, record) of one CPU run of the MoE serve cell at the tiny size."""
    from repro.coding.codec import get_codec

    return bench_tiny.bench_run.execute(
        bench_tiny.READ, seed, seconds, trace, require_chip=False, cache=False,
        codec=get_codec("jnp"), config=tiny(), traffic=copy.deepcopy(bench_tiny.SERVE_MIX),
        t_proc0=time.monotonic())
