"""Roofline-term extraction from AOT-compiled artifacts.

Three terms per (arch × shape × mesh), in seconds (v5e constants):

    compute    = HLO_FLOPs / (chips × 197e12 FLOP/s bf16)
    memory     = HLO_bytes / (chips × 819e9 B/s HBM)
    collective = collective_bytes / (chips × 50e9 B/s ICI per link)

HLO_FLOPs / bytes come from ``compiled.cost_analysis()``. Collective bytes
are NOT in cost_analysis: we parse the optimized HLO text and sum operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops.
"""

from __future__ import annotations

import dataclasses
import math
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Published per-chip peaks."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    source: str


#: Per-chip peaks keyed by ``jax.Device.device_kind``. A device that is not
#: listed has no roofline here: callers report None rather than borrow a row.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(197e12, 819e9, 'Google Cloud documentation, "TPU v5e"'),
}

# The dry-run models v5e pods.
PEAK_FLOPS = PEAKS["TPU v5 lite"].flops
HBM_BW = PEAKS["TPU v5 lite"].hbm_bw
ICI_BW = 50e9  # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in an HLO result-type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"  # result variable
    r"((?:\([^)]*\)|[\w\[\]\{\},:. ])+?)\s*"  # result type (may be a tuple)
    r"([a-z][a-z0-9\-]*)\("  # op name
)


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op in optimized HLO.

    Per-device semantics: in SPMD-partitioned HLO, op shapes are per-shard,
    so the sum approximates bytes moved through each device's links. Async
    pairs are counted once (the -start carries the buffers; -done skipped).

    Collectives are bucketed by where they live: ``region_*`` computations
    (while-loop bodies / control-flow regions — executed once per scanned
    layer/chunk, so they must be scaled by trip count) vs everything else
    (entry-level: FSDP epilogues, gradient all-reduce — executed once).
    """
    out: dict[str, int] = {c: 0 for c in _COLLECTIVES}
    out["count"] = 0
    out["in_loop"] = 0
    out["in_entry"] = 0
    current = "ENTRY"
    for line in hlo_text.splitlines():
        ls = line.strip()
        if ls.startswith("ENTRY"):
            current = "ENTRY"
            continue
        if ls.startswith("%") and ls.endswith("{") and "=" not in ls.split("(")[0]:
            current = ls.split(" ")[0]
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        base = op.removesuffix("-start")
        if op.endswith("-done"):
            continue
        if base in _COLLECTIVES:
            b = _shape_bytes(shape_str)
            if op.endswith("-start"):
                # start ops carry (input, output) tuples — halve.
                b //= 2
            out[base] += b
            out["count"] += 1
            if current.startswith("%region"):
                out["in_loop"] += b
            else:
                out["in_entry"] += b
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    hbm_bytes: float
    coll_bytes: float
    chips: int
    coll_breakdown: dict

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        # coll_bytes is per-shard already (SPMD HLO); one link assumed.
        return self.coll_bytes / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "coll_breakdown": {k: v for k, v in self.coll_breakdown.items() if v},
        }


def analyze(compiled, chips: int) -> Roofline:
    """Extract roofline terms from a jax compiled artifact."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    coll = collective_bytes(hlo)
    cbytes = float(coll.get("in_loop", 0) + coll.get("in_entry", 0))
    return Roofline(
        flops=flops, hbm_bytes=hbm, coll_bytes=cbytes, chips=chips,
        coll_breakdown=coll,
    )


def model_flops(cfg, shape, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (dense) or 6·N_active·D; decode: D = batch·1."""
    n_active = cfg.active_param_count()
    if kind == "train":
        tokens = shape.batch * shape.seq
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = shape.batch * shape.seq
        return 2.0 * n_active * tokens
    tokens = shape.batch * 1
    return 2.0 * n_active * tokens
