"""The cell `vol1g-x2-10p4-7vs-node-down.ycsb-c-spread-under-heal`: its
configuration and traffic against the spread cell's, its entries in
BENCHMARK.json, its five readers on documents worked by hand, and the
cell rehearsed at 8 MiB on the CPU with its control, two planted faults
and a program that keeps no count of a heal's sources."""

import io
import json
import os
import time
import types

import pytest

from ecbench import harness, roofline

CELL = "vol1g-x2-10p4-7vs-node-down.ycsb-c-spread-under-heal"
CONFIG = "vol1g-x2-10p4-7vs-node-down"
SMALL = {"volume_bytes": 8 << 20, "ec_interval_cache_mb": 1}
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}}
NS = 1e9
# what the heal adds to the records, with what each moves
HEAL = {
    "heal_mb_per_s": ("MB/s", "higher", "program_span", "peer-fetch rebuild"),
    "heal_source_s_per_gib": ("s/GiB", "lower", "program_span", "peer-fetch rebuild"),
    "heal_distribute_s_per_op": ("s", "lower", "program_span", "peer-fetch rebuild"),
    "heal_source_plane_share": ("%", "higher", "program_counter", "peer-fetch rebuild"),
    "heal_rs_roofline": ("%", "higher", "device_trace", "kernels"),
}
# the spread cell's metrics that mean here what they mean there, and the
# mixed cell's three of the queue (read at the rebuilder's scope)
SHARED = (
    "admission_wait_ms_per_get", "reconstruct_ms_per_get", "interval_cache_hit_share",
    "get_compiles_in_window", "get_cpu_ms_per_get", "ready_wait_ms_per_get",
    "frontend_self_ms_per_get", "sibling_read_ms_per_get", "crc_verify_ms_per_get",
    "rs_apply_ms_per_get", "sibling_batched_share", "reconstructing_get_share",
    "healthy_get_p50_ms", "shard_read_ms_per_get", "needle_parse_ms_per_get",
    "singleflight_wait_share", "interp_wait_ms", "core_wait_ms", "interp_wait_ms_per_get",
    "interp_returns_per_get", "other_python_cpu_share", "cores_busy",
    "peer_read_ms_per_get", "peer_reads_per_get", "remote_sibling_share",
    "peer_fetch_unused_share", "peer_request_ms_per_read", "peer_land_ms_per_read",
    "peer_fetch_queue_ms_per_read", "peer_wire_wait_ms_per_read",
    "peer_interp_wait_ms_per_read", "matrix_regather_share",
)
QUEUE = ("fg_blocked_by_recovery_share", "fg_blocked_by_recovery_ms_per_get", "recovery_slot_share")


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.fixture(scope="module")
def driver():
    return harness.load_module("drivers", "gets_under_heal_spread")


def reader(name):
    return harness.load_module("layers", name).read


# ------------------------------------------------- configuration and traffic


def test_the_deployment_is_the_spread_cells_with_a_second_volume_under_heal(manifest):
    configs = harness.HERE / "configs"
    mine = harness.load_json(configs / f"{CONFIG}.json")
    spread = harness.load_json(configs / "vol1g-10p4-7vs-node-down.json")
    for key in ("chips", "ec_backend", "ec_interval_cache_mb", "volume_bytes", "layout",
                "needles", "placement", "volume_servers", "down_server", "lost_shards",
                "reduced"):
        assert mine[key] == spread[key], key  # the same widths, layout and dead server
    assert mine["volumes"] == 2 and (mine["read_volume"], mine["heal_volume"]) == (1, 2)
    assert mine["guarantees"][: len(spread["guarantees"])] == spread["guarantees"]
    added = " ".join(mine["guarantees"][len(spread["guarantees"]):])
    assert "exactly one live server" in added and "fewer than k" in added
    assert ".ecsum" in added and "no source byte is written to disk" in added
    assert set(mine["cuts"]) == {"hosts"} and "one interpreter" in mine["cuts"]["hosts"]
    assert set(spread["assumed"]) < set(mine["assumed"])
    for key in ("reads_fall_on_the_volume_not_under_heal", "rebuilder", "sources_streamed"):
        assert mine["assumed"][key], key
    (entry,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["hosts"] and len(entry["source"]) <= 200
    for source in ("command_ec_rebuild.go", "store_ec.go", "BASELINE.json configs 2 and 4"):
        assert source in entry["source"], source


def test_the_traffic_is_the_spread_cells_to_the_letter_with_heals_beside():
    traffic = harness.load_json(harness.HERE / "traffic" / "ycsb-c-spread-under-heal.json")
    spread = harness.load_json(harness.HERE / "traffic" / "ycsb-c-spread.json")
    for key in spread:
        if key != "driver":
            assert traffic[key] == spread[key], key
    assert traffic["driver"] == "gets_under_heal_spread"
    assert (traffic["get_volume"], traffic["background_volume"]) == (1, 2)
    assert (traffic["background_op"], traffic["background_concurrency"]) == (
        "ec.rebuild -fromPeers", 1
    )
    assert traffic["heal_lost_shards"] == [1, 8] and traffic["background_from"] == "window_start"


def test_the_cell_is_judged_on_the_reads_and_listed_where_its_readers_mean_the_same(manifest):
    (cell,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert len(cell["why"]) <= 200
    assert manifest["workloads"][-1] == cell and manifest["configs"][-1]["name"] == CONFIG
    e2e = {m["name"]: harness.metric_cells(m, manifest) for m in manifest["end_to_end"]}
    assert {n for n, cells in e2e.items() if CELL in cells} == {
        "fg_p50_ms", "fg_p95_ms", "fg_ops_per_s", "setup_s",
    }
    listed = {m["name"] for m in manifest["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == set(HEAL) | set(SHARED) | set(QUEUE)
    # holders' spans mix the heal's 4 MiB ranges with the GETs' reads; the
    # device's kernel time and idle time hold the heals' batches too
    assert not listed & {"peer_serve_ms_per_read", "peer_serve_sendfile_share",
                         "rs_device_ms_per_get", "mixed_rs_roofline"}
    # the five stand at the end, in order, each listing this cell alone
    entries = manifest["per_layer"][-len(HEAL):]
    assert [m["name"] for m in entries] == list(HEAL)
    older = {m["layer"] for m in manifest["per_layer"][: -len(HEAL)]}
    for m in entries:
        unit, better, source, layer = HEAL[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (unit, better, source, layer)
        assert m["moves"] == "fg_p95_ms" and m["workloads"] == [CELL]
        assert (layer in older) == (layer == "kernels")
        assert callable(reader(m["name"]))
    for m in manifest["per_layer"]:
        if m["name"] in SHARED + QUEUE:
            assert m["workloads"][-1] == CELL, m["name"]


# ------------------------------------------------------------ the readers


def heal_doc(t0, length_s, plane, stream, local, staged=0, land=0.0, distribute=0.0):
    stages = {}
    if land:
        stages["source_land"] = {"seconds": land, "count": 2}
    if distribute:
        stages["distribute"] = {"seconds": distribute, "count": 1}
    heal = {
        "op": "ec.peer_rebuild", "duration_s": length_s, "start_ns": int(t0 * NS),
        "end_ns": int((t0 + length_s) * NS), "stages": stages, "events": [], "children": [],
        "attrs": {"source_bytes_plane": plane, "source_bytes_stream": stream,
                  "source_bytes_local": local, "source_bytes_staged": staged},
    }
    return {"op": "rpc.ec_shards_rebuild", "duration_s": length_s, "start_ns": int(t0 * NS),
            "end_ns": int((t0 + length_s) * NS), "stages": {}, "events": [], "attrs": {},
            "children": [heal]}


def get_doc(t0, up, down):
    recon = {"op": "ec.degraded_read", "duration_s": 0.03, "start_ns": int(t0 * NS),
             "end_ns": int((t0 + 0.03) * NS), "stages": {"reconstruct": {"seconds": 0.01}},
             "events": [], "children": [], "attrs": {"h2d_bytes": up, "d2h_bytes": down}}
    return {"op": "http.volume", "duration_s": 0.05, "start_ns": int(t0 * NS),
            "end_ns": int((t0 + 0.05) * NS), "stages": {}, "events": [],
            "attrs": {"op_class": "read"}, "children": [recon]}


def test_the_heal_readers_on_documents_worked_by_hand(manifest):
    """Two heals of 10 rows of 100 MB (eight from peers: 700 MB over the
    planes and 100 over the stream; two local) from 10.0 to 12.0 s and,
    overlapping it, 11.5 to 13.5 s; a GET that reconstructed 4 MB up and
    0.4 down at 12.5 s; a slice from 11.0 to 13.0 s, the device busy 0.5
    s of it."""
    row = 100_000_000
    docs = [
        heal_doc(10.0, 2.0, 7 * row, row, 2 * row, land=0.5, distribute=0.75),
        heal_doc(11.5, 2.0, 7 * row, row, 2 * row, land=0.3, distribute=1.25),
        get_doc(12.5, 4_000_000, 400_000),
    ]
    obs = types.SimpleNamespace(spans=docs, slice_t=(11.0, 13.0), device={"busy_s": 0.5})
    cell = harness.resolve_cell(manifest, CELL, 1, 20.0, True)
    cell.peaks, cell.device_kind = PEAKS, "TPU v5 lite"
    assert reader("heal_mb_per_s")(obs, cell) == pytest.approx(2 * 10 * row / 1e6 / 3.5)
    assert reader("heal_source_s_per_gib")(obs, cell) == pytest.approx(0.8 / (20 * row / 2**30))
    assert reader("heal_distribute_s_per_op")(obs, cell) == pytest.approx(1.0)
    assert reader("heal_source_plane_share")(obs, cell) == pytest.approx(87.5)
    # the first heal lies half inside the slice, the second three quarters
    healed = 10 * row * (0.5 + 0.75)
    nbytes = roofline.rs_bytes(healed, 10, 2) + 4_000_000 + 400_000
    ops = roofline.rs_ops(healed, 10, 2) + roofline.rs_ops(4_000_000, 10, 1)
    least = max(nbytes / 819e9, ops / 393e12)
    assert reader("heal_rs_roofline")(obs, cell) == pytest.approx(100 * least / 0.5)
    obs.device = None  # no device trace: no share
    assert reader("heal_rs_roofline")(obs, cell) is None


def test_a_program_that_records_no_heal_gives_the_readers_nothing(manifest):
    """An older program's documents: a heal whose span carries none of the
    source attributes and stages, and a window with no heal at all."""
    bare = heal_doc(1.0, 1.0, 0, 0, 0)
    for attr in list(bare["children"][0]["attrs"]):
        del bare["children"][0]["attrs"][attr]
    cell = harness.resolve_cell(manifest, CELL, 1, 20.0, True)
    cell.peaks, cell.device_kind = PEAKS, "TPU v5 lite"
    for docs in ([bare], [], [get_doc(1.0, 0, 0)]):
        obs = types.SimpleNamespace(spans=docs, slice_t=(0.0, 2.0), device={"busy_s": 0.5})
        for name in HEAL:
            assert reader(name)(obs, cell) is None, name


# ----------------------------------------------------------- the rehearsals


def run(manifest, traced=False, seconds=2.0, seed=2**31 + 51, **kw):
    out = io.StringIO()
    result = harness.run_cell(
        manifest, CELL, seed, seconds, traced, require_tpu=False,
        overrides=SMALL, out=out, **kw,
    )
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    assert not os.path.exists(harness.DATA_DIR)  # cleaned up
    return result


def test_a_traced_rehearsal_is_correct_and_reports_the_heals_metrics(manifest):
    result = run(manifest, traced=True, seconds=3.5)
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    for name in ("gets_wrong", "gets_failed", "no_healthy_get", "entry_servers_unused",
                 "shards_on_entry_server_only", "no_peer_read", "fallback_batches",
                 "backend_not_on_device", "heal_shard_files_differing",
                 "heal_ecsum_fields_differing", "heal_sets_not_compared", "duplicate_holders",
                 "heal_source_bytes_staged", "no_heal_source_from_peers", "no_heal_in_window",
                 "no_overlapped_pair", "classes_never_in_one_queue"):
        assert compared[name] == {"value": 0, "limit": 0}, name
    heals = compared["heals"]["value"]
    assert heals >= 2 and compared["heal_sets_compared_byte_by_byte"]["value"] == heals
    assert compared["heal_sources_from_peers"]["value"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert not {"volume_mb_per_s", "fg_p50_ms", "setup_s"} & set(metrics)  # per-layer only
    # no device here: the roofline finds nothing and says nothing
    assert set(HEAL) - {"heal_rs_roofline"} <= set(metrics)
    assert "heal_rs_roofline" not in metrics
    assert metrics["heal_source_plane_share"] == 100.0  # every peer has a plane
    assert metrics["heal_mb_per_s"] > 0 and metrics["heal_distribute_s_per_op"] > 0
    assert metrics["heal_source_s_per_gib"] >= 0
    for name in SHARED + QUEUE:
        assert isinstance(metrics.get(name), float), name
    assert metrics["remote_sibling_share"] == pytest.approx(80.0, abs=5.0)
    from seaweedfs_tpu.utils import trace

    trace.configure(enabled=False)


def test_an_untraced_rehearsal_reports_the_reads_and_no_rate_of_the_heals(manifest):
    result = run(manifest, seed=2**31 + 52)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"fg_p50_ms", "fg_p95_ms", "fg_ops_per_s", "setup_s"}
    assert result["attempted"] > result["compared"]["heals"]["value"] >= 2


def test_the_control_comes_out_as_not_correct(manifest):
    result = run(manifest, control=True, seed=2**31 + 53)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["gets_wrong"]["value"] > 0
    assert compared["heal_ecsum_fields_differing"]["value"] > 0


def test_a_source_staged_on_disk_and_a_healed_shard_altered_are_seen(manifest, driver, monkeypatch):
    """Two faults the program's own checks cannot see: a heal that writes
    a source row into its staging directory beside its targets (what the
    heal did before its sources streamed to the chip), and a byte of a
    regenerated shard changed where it landed after the heal."""
    from seaweedfs_tpu.ec import peer_rebuild

    real_attempt = peer_rebuild._attempt_rebuild

    def staging_attempt(base, ctx, *a, **kw):
        with open(base + ".ec02", "wb") as f:  # `base` lies in the staging directory
            f.write(b"\0" * 4096)
        time.sleep(0.1)
        return real_attempt(base, ctx, *a, **kw)

    real_keep = driver._keep

    def bent_keep(st):
        real_keep(st)
        base, _sidecars = st.heals.kept[-1]
        with open(base + ".ec01", "r+b") as f:
            first = f.read(1)
            f.seek(0)
            f.write(bytes([first[0] ^ 1]))

    monkeypatch.setattr(peer_rebuild, "_attempt_rebuild", staging_attempt)
    monkeypatch.setattr(driver, "_keep", bent_keep)
    result = run(manifest, seed=2**31 + 54)
    assert result["correct"] is False
    compared = result["compared"]
    assert compared["heal_source_bytes_staged"]["value"] >= 4096
    assert compared["heal_shard_files_differing"]["value"] == compared["heals"]["value"]
    assert compared["gets_wrong"]["value"] == 0  # the reads were not touched


def test_a_program_without_the_sources_count_fails_at_once(manifest, monkeypatch):
    """A program from before a heal's sources streamed to the chip: its
    heal stages its sources on disk and counts nothing of them. The run
    ends in set-up, before any volume is made, with no result."""
    from ecbench.cluster import BenchError
    from seaweedfs_tpu.utils import metrics

    monkeypatch.delattr(metrics, "ec_peer_rebuild_source_bytes_total")
    t0 = time.perf_counter()
    with pytest.raises(BenchError, match="sw_ec_peer_rebuild_source_bytes_total"):
        run(manifest)
    assert time.perf_counter() - t0 < 30
