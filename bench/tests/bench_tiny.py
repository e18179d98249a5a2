"""Small stand-ins for the benchmark's configurations and mixes, for CPU tests.

Every run here skips the harness's look for a chip and uses the CPU codecs
(the numpy oracle for storage, the jnp backend inside the fused serving
step); everything else is the run as the benchmark drives it.
"""

from __future__ import annotations

import copy
import importlib.util
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH), str(BENCH.parent / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchlib import manifest  # noqa: E402

# bench/run.py by its path: a module named "run" elsewhere cannot shadow it
_spec = importlib.util.spec_from_file_location("bench_run_cli", BENCH / "run.py")
bench_run = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_run)

READ = "s3-3mb.read.poisson"
PROMPT = "qwen1.5-0.5b.prompt-1k"
#: the write path runs as the read cell's configuration with a write mix
WRITE_MIX = {"op": "write", "rate_per_s": 40.0}


SERVE_MIX = {"generator": "closed_rounds", "clients": 4, "prompt_len": 16, "output_len": 4,
             "check_requests": 4, "trace_seconds": 0.5}


def tiny(workload: str) -> tuple[dict, dict]:
    """(config, traffic) of ``workload`` at a size a CPU test holds."""
    if workload == PROMPT:
        cfg = manifest.load_json(BENCH / "configs" / "qwen1.5-0.5b.json")
        # std 0.1 at width 64 gives each matmul about the gain that the
        # published std 0.02 gives at width 1024 (std * sqrt(width): 0.8, 0.64)
        cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=4, vocab_size=512,
                   initializer_range=0.1)
        cfg["prompt_store"].update(prompts=16, time_scale=0.02)
        cfg["limits"] = {"token_gap": 0.1}
        return cfg, copy.deepcopy(SERVE_MIX)
    found = manifest.resolve(workload)
    cfg, tr = copy.deepcopy(found["config"]), copy.deepcopy(found["traffic"])
    cfg.update(object_bytes=6 * 1024, objects=8, time_scale=0.02,
               layout={"K": 6, "r": 2, "strip_bytes": 1024})
    tr["max_codec_batch"] = 4
    return cfg, tr


def run_tiny(workload: str, seed: int = 3, seconds: float = 1.0, *, trace: bool = False,
             config: dict | None = None, traffic: dict | None = None):
    """(result, record) of one CPU run of ``workload`` at the tiny size."""
    from repro.coding.codec import get_codec

    cfg, tr = tiny(workload)
    if config:
        cfg.update(config)
    if traffic:
        tr.update(traffic)
    codec = get_codec("jnp") if cfg["driver"] == "serve" else None
    # the run takes the read cell's entry (one chip) and the given config and mix
    return bench_run.execute(READ, seed, seconds, trace, require_chip=False, cache=False,
                             codec=codec, config=cfg, traffic=tr,
                             t_proc0=time.monotonic())
