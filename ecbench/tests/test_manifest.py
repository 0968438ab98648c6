"""BENCHMARK.json against the files it names, and the harness's promise
that a cell arrives as data."""

import json
import re

import pytest

from ecbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def test_keys_are_exactly_the_contracts(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert manifest["command"] == ["python3", "ecbench/run.py"]
    assert manifest["paths"] == ["ecbench"]
    assert 1 <= manifest["run_seconds"] <= 51


def test_every_name_and_unit_is_of_the_permitted_characters(manifest):
    names = []
    for c in manifest["configs"]:
        names += [c["name"], *c["reduced"]]
    for w in manifest["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for n in names:
        assert NAME.match(n), n
    metric_names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_the_manifest_names_only_files_that_exist(manifest):
    for c in manifest["configs"]:
        path = harness.ROOT / c["file"]
        assert path.is_file(), path
        doc = json.loads(path.read_text())
        assert doc["reduced"] == c["reduced"]
        assert doc["guarantees"]
    for w in manifest["workloads"]:
        traffic = harness.HERE / "traffic" / f"{w['traffic']}.json"
        assert traffic.is_file(), traffic
        driver = json.loads(traffic.read_text())["driver"]
        assert (harness.HERE / "drivers" / f"{driver}.py").is_file()
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    for m in manifest["per_layer"]:
        assert (harness.HERE / "layers" / f"{m['name']}.py").is_file(), m["name"]


def test_every_metric_has_cells_that_report_what_it_moves(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: harness.metric_cells(m, manifest) for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for m in manifest["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert harness.metric_cells(m, manifest) <= cells
    for m in manifest["per_layer"]:
        assert harness.metric_cells(m, manifest) <= e2e[m["moves"]], m["name"]
        assert m["layer"]
    for cell in cells:
        mine = [n for n, where in e2e.items() if cell in where and n != "setup_s"]
        assert mine, f"{cell} reports no end-to-end metric besides setup_s"
        assert any(cell in harness.metric_cells(m, manifest) for m in manifest["per_layer"])
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(len(cells) // 2, 1)


def test_a_roofline_share_is_named_as_one(manifest):
    for m in manifest["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
            assert m["source"] == "device_trace"


def test_every_layer_reader_and_driver_loads(manifest):
    for m in manifest["per_layer"]:
        assert callable(harness.load_module("layers", m["name"]).read)
    for w in manifest["workloads"]:
        traffic = harness.load_json(harness.HERE / "traffic" / f"{w['traffic']}.json")
        driver = harness.load_module("drivers", traffic["driver"])
        for fn in ("setup", "window", "verify", "teardown"):
            assert callable(getattr(driver, fn))


def test_an_unknown_device_has_no_peaks():
    peaks = harness.load_json(harness.HERE / "peaks.json")
    assert "TPU v5 lite" in peaks and "cpu" not in peaks
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["int8_ops_per_s"] == 393e12
    assert peaks["TPU v5 lite"]["source"]
