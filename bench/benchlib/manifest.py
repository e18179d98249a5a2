"""Finds a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` at the checkout's root names every piece; each piece is a
file whose path follows from its name:

* configuration ``<c>``  -> the ``file`` its manifest entry gives,
* traffic mix ``<t>``    -> ``bench/traffic/<t>.json``,
* metric ``<m>``         -> ``bench/metrics/<m>.py`` (a ``read(run)`` function),
* driver ``<d>``         -> ``bench/benchlib/drivers/<d>.py`` (named by the
  configuration's ``driver`` key: one per kind of system, not per cell).

A later PR adds a configuration, mix or metric by adding such a file and a
manifest entry; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

#: bench/ (this file is bench/benchlib/manifest.py)
BENCH_DIR = Path(__file__).resolve().parents[1]
#: the checkout's root, which holds BENCHMARK.json
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


def check_name(name: str) -> str:
    """A name is 1-64 of [A-Za-z0-9_.-], starting with a letter, digit or _."""
    if not isinstance(name, str) or NAME_RE.fullmatch(name) is None:
        raise ValueError(f"bad name {name!r}: want [A-Za-z0-9_][A-Za-z0-9_.-]{{0,63}}")
    return name


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def cell(manifest: dict, name: str) -> dict:
    check_name(name)
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_path(manifest: dict, name: str, root: Path = ROOT) -> Path:
    check_name(name)
    for c in manifest["configs"]:
        if c["name"] == name:
            return Path(root) / c["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return Path(bench_dir) / "traffic" / f"{check_name(name)}.json"


def metric_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return Path(bench_dir) / "metrics" / f"{check_name(name)}.py"


def driver_path(name: str, bench_dir: Path = BENCH_DIR) -> Path:
    return Path(bench_dir) / "benchlib" / "drivers" / f"{check_name(name)}.py"


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise FileNotFoundError(f"{label}: no file {path}")
    mod_name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_", f"{label}_{path.stem}")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(run) -> float | None`` function of metric ``name``."""
    return _load_module(metric_path(name, bench_dir), "metric").read


def driver(name: str, bench_dir: Path = BENCH_DIR):
    """The driver module (``run(ctx) -> RunRecord``) a configuration names."""
    return _load_module(driver_path(name, bench_dir), "driver")


def cell_metrics(manifest: dict, cell_name: str, trace: bool) -> list[dict]:
    """The metric entries a run of ``cell_name`` reports.

    ``--trace 0`` reports the end-to-end metrics, ``--trace 1`` the
    per-layer ones. An entry with a ``workloads`` key is reported in those
    cells only; a per-layer entry without one is reported in every cell that
    reports the end-to-end metric it ``moves``.
    """
    def listed(m):
        return "workloads" not in m or cell_name in m["workloads"]

    e2e = [m for m in manifest["end_to_end"] if listed(m)]
    if not trace:
        return e2e
    e2e_names = {m["name"] for m in e2e}
    out = []
    for m in manifest["per_layer"]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif m["moves"] in e2e_names:
            out.append(m)
    return out


def resolve(workload: str, root: Path = ROOT) -> dict:
    """Everything one run of ``workload`` needs, found by name."""
    manifest = load_manifest(root)
    w = cell(manifest, workload)
    bench_dir = Path(root) / "bench"
    config = load_json(config_path(manifest, w["config"], root))
    traffic = load_json(traffic_path(w["traffic"], bench_dir))
    return {"manifest": manifest, "cell": w, "config": config, "traffic": traffic,
            "bench_dir": bench_dir}
