"""The cell `vol1g-x2-10p4-recovering.ycsb-c-under-rebuild`: its
configuration and traffic against the two cells it is made of, its four
readers on documents worked by hand and on ones recorded from a traced
v5e run of PR 33 (`mixed.span_docs.json`: the window's counters and
slice, and a few of its roots: healthy GETs, reconstructing ones that
found the window full of recovery batches and ones that did not, a
cache hit, two rebuilds), both controls, and a window in which the two
classes never met."""

import io
import json
import pathlib

import pytest

from ecbench import harness, roofline

HERE = pathlib.Path(__file__).resolve().parent
CELL = "vol1g-x2-10p4-recovering.ycsb-c-under-rebuild"
SMALL = {"volume_bytes": 8 << 20, "ec_interval_cache_mb": 0}
PEAKS = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}}
NEW = ("fg_blocked_by_recovery_share", "fg_blocked_by_recovery_ms_per_get",
       "recovery_slot_share", "mixed_rs_roofline")
NS = 1e9


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.fixture(scope="module")
def driver():
    return harness.load_module("drivers", "gets_under_rebuild")


def reader(name):
    return harness.load_module("layers", name).read


def cell_of(manifest):
    cell = harness.resolve_cell(manifest, CELL, 1, 20.0, True)
    cell.peaks, cell.device_kind = PEAKS, "TPU v5 lite"
    return cell


# ------------------------------------------- the files, against their parents


def test_the_configuration_is_the_node_down_one_twice_and_the_traffic_both_cells(manifest):
    configs = harness.HERE / "configs"
    mine = harness.load_json(configs / "vol1g-x2-10p4-recovering.json")
    down = harness.load_json(configs / "vol1g-10p4-node-down.json")
    for key in ("chips", "ec_backend", "ec_interval_cache_mb", "volume_bytes", "layout",
                "needles", "placement", "down_server", "lost_shards", "reduced"):
        assert mine[key] == down[key], key
    assert mine["volumes"] == 2 and mine["reduced"] == ["volume_servers"]
    assert set(down["guarantees"]) < set(mine["guarantees"])
    assert set(down["assumed"]) < set(mine["assumed"])
    traffic = harness.load_json(harness.HERE / "traffic" / "ycsb-c-under-rebuild.json")
    ycsb = harness.load_json(harness.HERE / "traffic" / "ycsb-c.json")
    for key in ycsb:
        if key != "driver":
            assert traffic[key] == ycsb[key], key
    assert traffic["get_lost_shards"] == traffic["rebuild_lost_shards"] == mine["lost_shards"]
    assert (traffic["get_volume"], traffic["background_volume"]) == (1, 2)
    assert (traffic["background_op"], traffic["background_concurrency"]) == ("ec.rebuild", 1)
    entry = next(c for c in manifest["configs"] if c["name"] == "vol1g-x2-10p4-recovering")
    assert len(entry["source"]) <= 200 and entry["reduced"] == mine["reduced"]


def test_the_cell_is_listed_where_its_readers_mean_what_they_say(manifest):
    listed = {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if CELL in m.get("workloads", ())
    }
    assert set(NEW) | {"fg_p50_ms", "fg_p95_ms", "fg_ops_per_s"} <= listed
    # each divides the whole device's busy time, or the whole window, by
    # ONE class's work: wrong beside a second class
    assert not listed & {
        "rs_roofline", "rs_device_s_per_gib", "rs_device_ms_per_get", "rs_glue_share_of_device",
        "frontend_ms_per_get", "compiles_in_window", "get_idle_unattributed_share",
    }
    for m in manifest["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL], m["name"]


# ------------------------------------------------------------ the readers


def degraded_read(t0, wait_s, stages=("reconstruct",), by=None, up=4_000_000, down=400_000,
                  length_s=0.030):
    stages = {s: {"seconds": 0.001, "count": 1, "chip": "", "cpu_s": 0.0} for s in stages}
    stages["admission_wait"] = {"seconds": wait_s, "count": 1, "chip": "", "cpu_s": 0.0}
    events = [] if by is None else [
        {"ts": 0.0, "name": "window_full", "attrs": {"by": by, "held": {by: 4}}}
    ]
    return {"op": "ec.degraded_read", "duration_s": length_s, "start_ns": int(t0 * NS),
            "end_ns": int((t0 + length_s) * NS), "attrs": {"h2d_bytes": up, "d2h_bytes": down},
            "stages": stages, "events": events, "children": []}


def get_root(*children):
    return {"op": "http.volume", "duration_s": 0.05, "attrs": {"op_class": "read"},
            "stages": {}, "events": [], "children": list(children)}


def hand_made():
    obs = harness.Observed()
    obs.spans = [
        get_root(),  # healthy
        get_root(degraded_read(10.5, 0.120, by="recovery")),  # whole in the slice
        get_root(degraded_read(12.0, 0.001)),  # reconstructs, found a free slot
        get_root(degraded_read(13.99, 0.050, by="foreground")),  # a third of it in the slice
        get_root(degraded_read(13.0, 0.0, stages=(), up=0, down=0)),  # a cache hit
        {"op": "rpc.ec_shards_rebuild", "duration_s": 1.0, "attrs": {}, "stages": {},
         "events": [], "children": []},
    ]
    obs.t_start, obs.t_end = 0.0, 20.0
    obs.counters.update(
        queue_window=4, queue_slot_seconds={"recovery": 24.0, "foreground": 1.0, "scrub": 0.0}
    )
    obs.ops = [("op", 2, 9.0, 11.0, 1_000_000_000), ("reset", 2, 11.0, 11.1, 0),
               ("op", 2, 12.0, 13.0, 1_000_000_000), ("op", 2, 15.0, 16.0, 1_000_000_000)]
    obs.slice_t = (10.0, 14.0)
    obs.device = {"busy_s": 0.05}
    return obs


def test_the_readers_on_documents_worked_by_hand(manifest):
    obs, cell = hand_made(), cell_of(manifest)
    # three GETs reconstructed; one of them laid its full window to recovery
    assert reader("fg_blocked_by_recovery_share")(obs, cell) == pytest.approx(100 / 3)
    # its 120 ms over all five GETs
    assert reader("fg_blocked_by_recovery_ms_per_get")(obs, cell) == pytest.approx(24.0)
    assert reader("recovery_slot_share")(obs, cell) == pytest.approx(100 * 24.0 / (4 * 20.0))
    # half of the first rebuild and all of the second; two reconstructions
    # whole and a third of another
    rebuilt = 1.5e9
    up, down = (2 + 1 / 3) * 4e6, (2 + 1 / 3) * 4e5
    nbytes = rebuilt * 12 / 10 + up + down
    assert roofline.rs_bytes(rebuilt, 10, 2) == pytest.approx(rebuilt * 12 / 10)
    assert reader("mixed_rs_roofline")(obs, cell) == pytest.approx(
        100 * (nbytes / 819e9) / 0.05, rel=1e-6
    )
    # bytes bound it: the int8 unit would be done sooner
    assert nbytes / 819e9 > (roofline.rs_ops(rebuilt, 10, 2) + roofline.rs_ops(up, 10, 1)) / 393e12


def test_a_window_with_one_class_only_still_has_a_roofline_share(manifest):
    obs, cell = hand_made(), cell_of(manifest)
    obs.ops = []
    up, down = (2 + 1 / 3) * 4e6, (2 + 1 / 3) * 4e5
    assert reader("mixed_rs_roofline")(obs, cell) == pytest.approx(
        100 * ((up + down) / 819e9) / 0.05, rel=1e-6
    )
    obs = hand_made()
    obs.spans = obs.spans[:1]
    assert reader("mixed_rs_roofline")(obs, cell) == pytest.approx(
        100 * (1.5e9 * 1.2 / 819e9) / 0.05, rel=1e-6
    )
    obs.ops = []
    assert reader("mixed_rs_roofline")(obs, cell) is None


def test_a_program_whose_queue_says_nothing_gives_the_new_readers_nothing(manifest):
    """The parent of PR 33: no slot-seconds, no `window_full` event."""
    obs, cell = hand_made(), cell_of(manifest)
    del obs.counters["queue_slot_seconds"]
    for d in obs.spans:
        for child in d["children"]:
            child["events"] = []
    for name in NEW[:3]:
        assert reader(name)(obs, cell) is None, name
    assert reader("mixed_rs_roofline")(obs, cell) > 0  # spans and bytes that were always there
    for name in NEW:
        assert reader(name)(harness.Observed(), cell) is None, name
    # no device (the CPU rehearsal), an unknown device: never a guess
    obs = hand_made()
    obs.device = None
    assert reader("mixed_rs_roofline")(obs, cell) is None
    cell.device_kind = "cpu"
    assert reader("mixed_rs_roofline")(hand_made(), cell) is None


RECORDED = HERE / "mixed.span_docs.json"


@pytest.fixture(scope="module")
def recorded():
    return json.loads(RECORDED.read_text())


def obs_of(doc):
    obs = harness.Observed()
    obs.spans = doc["spans"]
    obs.t_start, obs.t_end = doc["t_start"], doc["t_end"]
    obs.counters.update(doc["counters"])
    obs.ops = [tuple(o) for o in doc["ops"]]
    obs.slice_t = tuple(doc["slice_t"])
    obs.device = {"busy_s": doc["busy_s"]}
    return obs


def test_the_readers_on_the_recorded_documents(recorded, manifest):
    """The answers below were worked out by hand from the file (the sums
    are its `by_hand` block, written down when it was recorded)."""
    obs, cell = obs_of(recorded), cell_of(manifest)
    by_hand = recorded["by_hand"]
    gets = [d for d in obs.spans if d["op"] == "http.volume"]
    assert len(gets) == by_hand["gets"] and by_hand["reconstructing"] >= 2
    assert by_hand["blocked_by_recovery"] >= 1
    assert reader("fg_blocked_by_recovery_share")(obs, cell) == pytest.approx(
        100.0 * by_hand["blocked_by_recovery"] / by_hand["reconstructing"]
    )
    assert reader("fg_blocked_by_recovery_ms_per_get")(obs, cell) == pytest.approx(
        1e3 * by_hand["blocked_wait_s"] / by_hand["gets"]
    )
    assert reader("recovery_slot_share")(obs, cell) == pytest.approx(
        100.0 * by_hand["recovery_slot_s"] / (by_hand["window"] * by_hand["wall_s"])
    )
    assert reader("mixed_rs_roofline")(obs, cell) == pytest.approx(
        100.0 * (by_hand["bytes_needed"] / 819e9) / recorded["busy_s"], rel=1e-6
    )
    assert 0 < reader("mixed_rs_roofline")(obs, cell) <= 100
    # the accepted readers of both classes read the same documents
    for name in ("admission_wait_ms_per_get", "reconstruct_ms_per_get",
                 "reconstructing_get_share", "pipeline_cpu_s_per_gib", "io_s_per_gib"):
        obs.bytes = sum(o[4] for o in obs.ops if o[0] == "op")
        assert reader(name)(obs, cell) > 0, name


def test_the_recorded_event_lies_on_the_span_that_waited(recorded):
    from ecbench.layerlib import walk

    reads = [d for r in recorded["spans"] for d in walk(r) if d["op"] == "ec.degraded_read"]
    full = [d for d in reads if any(e["name"] == "window_full" for e in d["events"])]
    assert full
    for d in full:
        (ev,) = [e for e in d["events"] if e["name"] == "window_full"]
        assert ev["attrs"]["by"] in ("recovery", "foreground")
        assert sum(ev["attrs"]["held"].values()) == recorded["counters"]["queue_window"]
        assert d["stages"]["admission_wait"]["seconds"] > 0
    batches = [
        e for r in recorded["spans"] if r["op"] == "rpc.ec_shards_rebuild"
        for d in walk(r) for e in d["events"] if e["name"] == "window_full"
    ]
    assert all(e["attrs"]["by"] in ("recovery", "foreground") for e in batches)


# --------------------------------------------- runs at 8 MiB, on the CPU


def run(manifest, **kw):
    return harness.run_cell(
        manifest, CELL, 2**31 + 33, 1.5, False, require_tpu=False, overrides=SMALL,
        out=io.StringIO(), **kw,
    )


def over(result):
    return {n for n, c in result["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]}


def test_a_run_is_correct_by_both_drivers_comparisons_and_both_controls_are_not(manifest):
    result = run(manifest)
    assert result["correct"] is True, result["compared"]
    compared = result["compared"]
    assert compared["overlapped_pairs"]["value"] > 0 and compared["rebuilds"]["value"] >= 1
    # every GET and every rebuilt set went through its own driver's comparison
    assert compared["gets_compared"]["value"] + compared["rebuilds"]["value"] == result["attempted"]
    assert compared["shard_sets_compared_byte_by_byte"]["value"] == compared["rebuilds"]["value"]
    assert compared["sidecars_compared"]["value"] == compared["rebuilds"]["value"]
    assert compared["gets_reconstructing"]["value"] > 0
    assert list(compared).count("fallback_batches") == 1
    broken = run(manifest, control=True)
    assert broken["correct"] is False
    # a product left out of a parity row (both lost shards are data
    # shards, which the broken reference stripes as the true one does:
    # the `.ecsum`, which covers all fourteen, is where it shows); every
    # key answered with its neighbour's body
    assert {"ecsum_fields_differing", "gets_wrong"} <= over(broken)


def test_a_window_in_which_the_classes_never_met_is_not_correct(manifest, driver, monkeypatch):
    monkeypatch.setattr(driver, "overlap", lambda st: (0, 1))
    result = run(manifest)
    assert result["correct"] is False and over(result) == {"no_overlapped_pair"}
    monkeypatch.setattr(driver, "overlap", lambda st: (7, 2))  # reads on another queue
    result = run(manifest)
    assert result["correct"] is False and over(result) == {"classes_not_in_one_queue"}
