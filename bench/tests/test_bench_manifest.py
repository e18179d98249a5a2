"""The loader finds every piece of every cell by name, and the manifest keeps
to the benchmark's contract."""

import json
import re

import bench_tiny
import pytest

from benchlib import manifest

MANIFEST = manifest.load_manifest()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
METRICS = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    found = manifest.resolve(cell)
    assert found["config"]["name"] == found["cell"]["config"]
    assert manifest.driver_path(found["config"]["driver"]).is_file()
    assert manifest.traffic_path(found["cell"]["traffic"]).is_file()
    for trace in (False, True):
        for m in manifest.cell_metrics(MANIFEST, cell, trace):
            assert callable(manifest.metric_reader(m["name"]))
    names = {m["name"] for m in manifest.cell_metrics(MANIFEST, cell, False)}
    assert "setup_s" in names and len(names) >= 2
    assert manifest.cell_metrics(MANIFEST, cell, True)


@pytest.mark.parametrize("name", METRICS)
def test_every_metric_has_a_reader(name):
    assert callable(manifest.metric_reader(name))


@pytest.mark.parametrize("bad", ["", "a/b", "../x", "a b", ".hidden", "x" * 65, "é", "a,b"])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        manifest.check_name(bad)
    with pytest.raises(ValueError):
        manifest.traffic_path(bad)
    with pytest.raises(ValueError):
        manifest.metric_path(bad)


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        manifest.cell(MANIFEST, "no.such.cell")


def test_per_layer_metrics_follow_their_end_to_end_metric():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", []):
            reported = {x["name"] for x in manifest.cell_metrics(MANIFEST, cell, False)}
            assert m["moves"] in reported, (m["name"], cell)


def test_manifest_keeps_to_the_contract():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["bench"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    name_re = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
    unit_re = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert name_re.fullmatch(m["name"]) and unit_re.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in MANIFEST["configs"]:
        assert c["file"].startswith("bench/")
        assert json.loads(open(bench_tiny.BENCH.parent / c["file"]).read())["name"] == c["name"]
    for w in MANIFEST["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(json.dumps(MANIFEST)) < 64 * 1024
