"""Every cell through the harness's Python entry at 8 MiB on the CPU
(the command itself fails without a TPU), the control and the planted
faults seen as not correct, and a cell arriving as data."""

import io
import json
import os
import subprocess
import sys

import pytest

from ecbench import harness

SMALL = {"volume_bytes": 8 << 20, "ec_interval_cache_mb": 0}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def run(manifest, cell, traced=False, seconds=1.5, **kw):
    out = io.StringIO()
    result = harness.run_cell(
        manifest, cell, 2**31 + 41, seconds, traced, require_tpu=False,
        overrides=SMALL, out=out, **kw,
    )
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    assert list(result)[-1] == "compared"  # each number beside its limit, last
    assert not os.path.exists(harness.DATA_DIR)  # cleaned up
    assert not os.path.exists(harness.TRACE_DIR)
    return result


def cells(manifest):
    return [w["name"] for w in manifest["workloads"]]


def test_every_cell_runs_correct_and_reports_its_end_to_end_metrics(manifest):
    for cell in cells(manifest):
        result = run(manifest, cell)
        assert result["correct"] is True, result["compared"]
        assert result["attempted"] > 0 and result["failed"] == 0
        want = {
            m["name"] for m in manifest["end_to_end"]
            if cell in harness.metric_cells(m, manifest)
        }
        assert set(result["metrics"]) == want
        assert all(v["value"] > 0 for v in result["metrics"].values())
        assert result["device"]["platform"] == "cpu"  # and said so


def test_a_traced_run_reports_per_layer_metrics_only(manifest):
    for cell in cells(manifest):
        result = run(manifest, cell, traced=True, seconds=3.5)
        assert result["correct"] is True
        listed = {
            m["name"] for m in manifest["per_layer"]
            if cell in harness.metric_cells(m, manifest)
        }
        assert set(result["metrics"]) <= listed
        # no device here: its readers find nothing and say nothing
        assert not {"rs_roofline", "rs_device_s_per_gib", "rs_device_ms_per_get"} & set(
            result["metrics"]
        )
        assert {"busy_s", "window_s"} <= set(result["device"])
        assert result["breakdown"]["idle_gaps"]


def test_the_control_comes_out_as_not_correct(manifest):
    for cell in cells(manifest):
        result = run(manifest, cell, control=True)
        assert result["correct"] is False, cell
        over = [n for n, c in result["compared"].items()
                if c["limit"] is not None and c["value"] > c["limit"]]
        assert over


def test_an_answer_altered_where_it_is_produced_is_not_correct(manifest, monkeypatch):
    """The timed path broken underneath: the device backend hands back
    parity with one byte changed; a GET's body has one byte changed."""
    from seaweedfs_tpu.ec import backend as B
    from seaweedfs_tpu.storage import store as S

    real_to_host = B.JaxBackend.to_host

    def bent_to_host(self, arr):
        import numpy as np

        out = np.array(real_to_host(self, arr))
        out[0, 0] ^= 1
        return out

    real_read = S.Store.read_needle

    def bent_read(self, *a, **kw):
        n = real_read(self, *a, **kw)
        n.data = bytes([n.data[0] ^ 1]) + n.data[1:]
        return n

    for cell in cells(manifest):
        with monkeypatch.context() as mp:
            if "get" in cell:
                mp.setattr(S.Store, "read_needle", bent_read)
                with pytest.raises(harness_error()):
                    run(manifest, cell)  # the warm-up sweep already sees it
                mp.undo()
                # past the warm-up: bend it for the window only
                result = run_with_window_fault(manifest, cell, mp, S.Store, "read_needle", bent_read)
            else:
                mp.setattr(B.JaxBackend, "to_host", bent_to_host)
                try:
                    result = run(manifest, cell)
                except harness_error() as e:
                    # ec.rebuild checks what it regenerated against the
                    # sidecar and refuses to publish: no result at all
                    assert "fails sidecar verification" in str(e), e
                    continue
            assert result["correct"] is False, cell


def harness_error():
    from ecbench.cluster import BenchError

    return BenchError


def run_with_window_fault(manifest, cell, mp, owner, name, bent):
    """Plant the fault when the window opens, not before."""
    traffic = harness.load_json(
        harness.HERE / "traffic" / f"{cell.split('.', 1)[1]}.json"
    )
    driver = harness.load_module("drivers", traffic["driver"])
    real_window = driver.window

    def window(c, st, sl):
        mp.setattr(owner, name, bent)
        return real_window(c, st, sl)

    mp.setattr(driver, "window", window)
    return run(manifest, cell)


def test_an_altered_rebuilt_shard_is_not_correct(manifest, monkeypatch):
    """The program's own check cannot see this one: a byte of a rebuilt
    shard changes on disk after ec.rebuild has published it."""
    driver = harness.load_module("drivers", "volume_ops")
    real_do = driver._do

    def bent_do(st, vid, op=None):
        real_do(st, vid, op)
        if (op or st.op) == "ec.rebuild":
            vol = next(v for v in st.volumes if v.vid == vid)
            with open(vol.base + ".ec03", "r+b") as f:
                first = f.read(1)
                f.seek(0)
                f.write(bytes([first[0] ^ 1]))

    monkeypatch.setattr(driver, "_do", bent_do)
    result = run(manifest, "vol1g-10p4.rebuild")
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["shard_files_differing"]["value"] == result["attempted"]
    assert compared["shard_sets_not_compared"]["value"] == 0


@pytest.mark.parametrize("traffic, why", [
    ({"driver": "volume_ops", "op": "ec.rebuild", "concurrency": 1,
      "lost_shards": [0, 13]}, "shards 0 and 13 lost, ec.rebuild"),
    ({"driver": "volume_ops", "op": "ec.encode", "concurrency": 1},
     "ec.encode, one after another"),
])
def test_a_volume_cell_arrives_as_one_traffic_file_and_one_entry(manifest, traffic, why):
    before = {p: p.stat().st_mtime_ns for p in harness.HERE.rglob("*") if p.is_file()}
    path = harness.HERE / "traffic" / "tmp-arrives.json"
    path.write_text(json.dumps(traffic))
    try:
        grown = json.loads(json.dumps(manifest))
        name = "vol1g-10p4.tmp-arrives"
        grown["workloads"].append({
            "name": name, "config": "vol1g-10p4", "traffic": "tmp-arrives",
            "chips": 1, "why": why,
        })
        for m in grown["end_to_end"] + grown["per_layer"]:
            if m["name"] == "volume_mb_per_s" or m.get("moves") == "volume_mb_per_s":
                m["workloads"].append(name)
        result = run(grown, name)
        assert result["correct"] is True
        sets = result["attempted"] if traffic["op"] == "ec.rebuild" else 1
        assert result["compared"]["shard_sets_compared_byte_by_byte"]["value"] == sets
        assert result["compared"]["sidecars_compared"]["value"] == result["attempted"]
        assert result["metrics"]["volume_mb_per_s"]["value"] > 0
        assert run(grown, name, control=True)["correct"] is False
    finally:
        path.unlink()
    after = {p: p.stat().st_mtime_ns for p in harness.HERE.rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert {p: t for p, t in before.items() if "__pycache__" not in p.parts} == after


def test_the_command_fails_without_a_tpu_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "ecbench/run.py", "--workload", "vol1g-10p4.rebuild",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"},
        capture_output=True, text=True, timeout=180, cwd=str(harness.ROOT),
    )
    assert out.returncode != 0
    assert "no accelerator" in out.stderr
    assert '"correct"' not in out.stdout


def test_the_command_fails_where_only_the_benchmark_is(tmp_path):
    import shutil

    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(
        harness.HERE, tmp_path / "ecbench",
        ignore=shutil.ignore_patterns("__pycache__", "_data", "_trace"),
    )
    out = subprocess.run(
        [sys.executable, "ecbench/run.py", "--workload", "vol1g-10p4.rebuild"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=180, cwd=str(tmp_path),
    )
    assert out.returncode != 0
    assert '"correct"' not in out.stdout
