"""The benchmark's spec: every cell resolves by name to its files, a
missing one is an error, and BENCHMARK.json keeps the contract's
shape."""
import dataclasses
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_entries_keep_their_shapes():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    units = [m["unit"] for k in ("end_to_end", "per_layer") for m in SPEC[k]]
    assert all(UNIT.match(u) for u in units)
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_and_reports(cell):
    c = harness.find_cell(SPEC, cell)
    harness.load_driver(c.traffic["driver"])
    for m in c.per_layer:
        harness.load_reader(m["name"])
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert all(m["moves"] in e2e for m in c.per_layer)


def test_every_metric_has_a_cell():
    e2e_cells = {m["name"]: set(m.get("workloads", CELLS))
                 for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert set(m["workloads"]) <= e2e_cells[m["moves"]]


def _copy(tmp_path):
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return tmp_path / "bench"


@pytest.mark.parametrize("missing", ["configs/sift1m.json",
                                     "traffic/fit.json"])
def test_missing_file_is_an_error(tmp_path, missing):
    b = _copy(tmp_path)
    (b / missing).unlink()
    with pytest.raises(harness.SpecError):
        harness.find_cell(SPEC, "sift1m.fit", bench_dir=b)


def test_missing_driver_or_reader_is_an_error(tmp_path):
    b = _copy(tmp_path)
    (b / "drivers" / "fit.py").unlink()
    (b / "metrics" / "fit.sort_share.py").unlink()
    with pytest.raises(harness.SpecError):
        harness.load_driver("fit", bench_dir=b)
    with pytest.raises(harness.SpecError):
        harness.load_reader("fit.sort_share", bench_dir=b)


def test_unknown_names_are_errors():
    with pytest.raises(harness.SpecError):
        harness.find_cell(SPEC, "no.such.cell")
    spec = dict(SPEC, workloads=[dict(SPEC["workloads"][0],
                                      config="nope")])
    with pytest.raises(harness.SpecError):
        harness.find_cell(spec, SPEC["workloads"][0]["name"])


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.UnknownDeviceError):
        harness.peaks_for("TPU v9 imaginary")


def test_metrics_of_a_cell_follow_workloads_keys():
    spec = {"end_to_end": [{"name": "setup_s"},
                           {"name": "a", "workloads": ["x"]},
                           {"name": "b", "workloads": ["y"]}],
            "per_layer": [{"name": "la", "moves": "a", "workloads": ["x"]},
                          {"name": "lb", "moves": "b", "workloads": ["y"]}]}
    e2e, pl = harness.cell_metrics(spec, "x")
    assert [m["name"] for m in e2e] == ["setup_s", "a"]
    assert [m["name"] for m in pl] == ["la"]


def test_per_layer_metric_without_workloads_is_an_error():
    spec = {"end_to_end": [{"name": "setup_s"}],
            "per_layer": [{"name": "la", "moves": "setup_s"}]}
    with pytest.raises(harness.SpecError):
        harness.cell_metrics(spec, "x")


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_config_spells_out_the_fit(name):
    from repro import GeekConfig
    cfg = harness.load_json(harness.BENCH_DIR / "configs" / f"{name}.json")
    assert set(cfg["fit"]) == {f.name for f in
                               dataclasses.fields(GeekConfig)}
    entry = next(c for c in SPEC["configs"] if c["name"] == name)
    assert all(k in cfg for k in entry["reduced"])
    assert entry["source"] in cfg["source"]
