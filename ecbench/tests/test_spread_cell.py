"""The cell `vol1g-10p4-7vs-node-down.ycsb-c-spread`: its configuration
against the one it shares a volume with, its entries in BENCHMARK.json,
its entry-server draws, its five readers on kept and hand-worked span
documents, the holders' bytes counted on either plane, and the cell
rehearsed at 8 MiB on the CPU with its control and two planted faults."""

import io
import json
import os
import pathlib
import shutil
import time
import types

import numpy as np
import pytest

from ecbench import harness

HERE = pathlib.Path(__file__).resolve().parent
CELL = "vol1g-10p4-7vs-node-down.ycsb-c-spread"
CONTROL = "vol1g-10p4-node-down.ycsb-c"
SHARED = (
    "admission_wait_ms_per_get", "reconstruct_ms_per_get", "interval_cache_hit_share",
    "get_compiles_in_window", "get_cpu_ms_per_get", "ready_wait_ms_per_get",
    "frontend_self_ms_per_get", "sibling_read_ms_per_get", "crc_verify_ms_per_get",
    "rs_apply_ms_per_get", "sibling_batched_share", "rs_device_ms_per_get",
    "rs_glue_share_of_device", "get_idle_unattributed_share",
)
# what the spread adds to the program's records (layer `peer shard
# reads`, this cell alone), in ISSUE 36's order with what each moves
PEERS = {
    "peer_read_ms_per_get": ("ms", "program_span", "fg_p50_ms"),
    "peer_reads_per_get": ("1", "program_counter", "fg_ops_per_s"),
    "peer_serve_ms_per_read": ("ms", "program_span", "fg_p50_ms"),
    "remote_sibling_share": ("%", "program_counter", "fg_p95_ms"),
    "peer_fetch_unused_share": ("%", "program_counter", "fg_ops_per_s"),
}
# the probe metrics of the GET cells and the metrics that were `ycsb-c`'s
# alone: the cell is on their lists since ISSUE 36
PROBES = (
    "interp_wait_ms", "core_wait_ms", "interp_wait_ms_per_get", "interp_returns_per_get",
    "other_python_cpu_share", "cores_busy",
)
YCSB = (
    "reconstructing_get_share", "healthy_get_p50_ms", "shard_read_ms_per_get",
    "needle_parse_ms_per_get", "singleflight_wait_share",
)
SMALL = {"volume_bytes": 8 << 20, "ec_interval_cache_mb": 1}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.fixture(scope="module")
def driver():
    return harness.load_module("drivers", "http_gets_ycsb_spread")


def reader(name):
    return harness.load_module("layers", name).read


def take(stream, n):
    return [next(stream) for _ in range(n)]


# ------------------------------------------------- configuration and traffic


def test_the_deployment_is_node_downs_with_the_shards_left_where_they_lie(manifest):
    mine = harness.load_json(harness.HERE / "configs" / "vol1g-10p4-7vs-node-down.json")
    local = harness.load_json(harness.HERE / "configs" / "vol1g-10p4-node-down.json")
    for key in ("volume_bytes", "volumes", "layout", "needles", "placement", "ec_backend",
                "ec_interval_cache_mb", "chips", "down_server", "lost_shards"):
        assert mine[key] == local[key], key  # the control differs in nothing else
    assert mine["guarantees"][: len(local["guarantees"])] == local["guarantees"]
    added = " ".join(mine["guarantees"][len(local["guarantees"]):])
    assert "generation fence" in added and ".ecsum" in added
    assert mine["reduced"] == ["hosts"] and set(mine["cuts"]) == {"hosts"}
    assert "one interpreter" in mine["cuts"]["hosts"] and "one chip" in mine["cuts"]["hosts"]
    assert set(local["assumed"]) < set(mine["assumed"])
    assert mine["volume_servers"] == len(mine["placement"]["shards_of_server"]) == 7
    entry = next(c for c in manifest["configs"] if c["name"] == "vol1g-10p4-7vs-node-down")
    assert entry["reduced"] == ["hosts"] and len(entry["source"]) <= 200
    assert "VolumeEcShardRead" in entry["source"] and "ec.balance" in entry["source"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["config"] == "vol1g-10p4-7vs-node-down"
    assert not any(w["chips"] == 4 for w in manifest["workloads"])


def test_the_traffic_is_ycsb_cs_with_an_entry_server_drawn_per_request():
    mine = harness.load_json(harness.HERE / "traffic" / "ycsb-c-spread.json")
    local = harness.load_json(harness.HERE / "traffic" / "ycsb-c.json")
    for key in ("clients", "read_proportion", "keys", "request_distribution",
                "zipfian_constant", "popularity_seed"):
        assert mine[key] == local[key], key  # the same clients, keys and hot set
    assert mine["clients"] == 16 and mine["popularity_seed"] == 24
    assert mine["driver"] == "http_gets_ycsb_spread"
    assert mine["entry_server"] == "uniform_live_holder"
    assert mine["warm_sweep"] == "on_lost_through_each_live_server"
    assert mine["warm_draws"] == 3000 and mine["down_server"] == 1


def test_the_cell_is_listed_where_its_readers_find_something(manifest):
    listed = {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if CELL in m.get("workloads", ())
    }
    assert listed == (
        set(SHARED) | set(PEERS) | set(PROBES) | set(YCSB)
        | {"fg_p50_ms", "fg_p95_ms", "fg_ops_per_s"}
    )
    for m in manifest["per_layer"]:
        if m["name"] in SHARED + PROBES + YCSB:
            assert m["workloads"][-1] == CELL and CONTROL in m["workloads"]
    assert manifest["configs"][-1]["name"] == CELL.split(".")[0]
    assert manifest["workloads"][-1]["name"] == CELL


def test_the_five_readers_are_entries_of_a_layer_of_their_own_at_the_end(manifest):
    entries = manifest["per_layer"][-len(PEERS):]
    assert [m["name"] for m in entries] == list(PEERS)
    older = {m["layer"] for m in manifest["per_layer"][: -len(PEERS)]}
    reported = {e["name"]: harness.metric_cells(e, manifest) for e in manifest["end_to_end"]}
    for m in entries:
        unit, source, moves = PEERS[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (unit, "lower", source, moves)
        assert m["layer"] == "peer shard reads" and m["layer"] not in older
        assert m["workloads"] == [CELL] and CELL in reported[moves]
        assert callable(reader(m["name"]))


def test_entry_draws_are_uniform_seeded_and_on_a_stream_of_their_own(driver):
    n, live = 60_000, 6
    a = take(driver.entry_draws(2**31 + 35, 0, live), n)
    assert a == take(driver.entry_draws(2**31 + 35, 0, live), n)
    assert a != take(driver.entry_draws(2**31 + 35, 1, live), n)
    assert a != take(driver.entry_draws(2**31 + 36, 0, live), n)
    counts = np.bincount(a, minlength=live)
    sigma = (n * (1 / live) * (1 - 1 / live)) ** 0.5
    assert set(a) == set(range(live)) and np.all(np.abs(counts - n / live) <= 4 * sigma)
    # independent of the key's stream: the same client and seed, another
    # generator; the hottest needle enters at every server alike
    traffic = harness.load_json(harness.HERE / "traffic" / "ycsb-c-spread.json")
    cell = types.SimpleNamespace(seed=2**31 + 35, traffic=traffic)
    keys = take(driver.Y.needle_stream(cell, 0, 1324), n)
    hottest = max(set(keys[:4000]), key=keys[:4000].count)
    at = np.bincount([s for s, k in zip(a, keys) if k == hottest], minlength=live)
    assert at.min() > 0.8 * at.mean()
    assert driver.Y.WARM_CLIENT >= 64  # the warm-up's streams are no client's


# ---------------------------------------------------- the five new readers


def obs_of(docs):
    return types.SimpleNamespace(spans=docs)


def doc(op, duration_s=0.0, stages=None, attrs=None, children=()):
    return {
        "op": op, "name": op, "duration_s": duration_s, "events": [],
        "attrs": dict(attrs or {}),
        "stages": {s: {"seconds": t, "count": 1} for s, t in (stages or {}).items()},
        "children": list(children),
    }


def get_root(**kw):
    attrs = {"op_class": "read", **kw.pop("attrs", {})}
    return doc("http.volume", attrs=attrs, **kw)


def test_the_readers_on_documents_worked_by_hand():
    """Four GETs: one with every interval here, one that read two
    intervals from peers, one that reconstructed with eight rows from
    ten fetches, one that found its extent cached; five streams seen at
    the holders."""
    recon = doc(
        "ec.degraded_read", 0.5,
        stages={"peer_read": 0.300, "sibling_read": 0.005, "crc_verify": 0.02},
        attrs={"sibling_rows_batched": 2, "sibling_rows_single": 8, "sibling_rows_remote": 8,
               "peer_fetches_started": 10, "peer_fetches_unused": 2},
    )
    hit = doc("ec.degraded_read", 0.001)
    docs = [
        get_root(duration_s=0.04, stages={"volume.read": 0.03, "volume.read.shard": 0.01}),
        get_root(duration_s=0.3, stages={"peer_read": 0.250, "volume.read.peer": 0.251},
                 attrs={"peer_reads": 2, "peer_read_bytes": 900_000}),
        get_root(duration_s=0.7, stages={"peer_read": 0.050}, children=[recon],
                 attrs={"peer_reads": 1, "peer_read_bytes": 400_000}),
        get_root(duration_s=0.1, children=[hit]),
        doc("http.volume", 9.0, attrs={"op_class": "status"}),  # no GET of a needle
    ] + [doc("rpc.ec_shard_read", t, stages={"stream": t}) for t in (0.01, 0.02, 0.03, 0.04, 0.10)]
    obs = obs_of(docs)
    assert reader("peer_read_ms_per_get")(obs, None) == pytest.approx(1e3 * 0.600 / 4)
    assert reader("peer_reads_per_get")(obs, None) == pytest.approx((2 + 1 + 10) / 4)
    assert reader("peer_serve_ms_per_read")(obs, None) == pytest.approx(40.0)
    assert reader("remote_sibling_share")(obs, None) == pytest.approx(80.0)
    assert reader("peer_fetch_unused_share")(obs, None) == pytest.approx(20.0)


def test_a_program_that_records_none_of_it_gives_the_readers_nothing_and_they_do_not_raise():
    """The parent's documents: a peer's interval lies in `.shard`, a
    reconstruction's wait for peers in `sibling_read`, rows from peers
    are `sibling_rows_single`; only the holders' spans are there."""
    recon = doc("ec.degraded_read", 0.5, stages={"sibling_read": 0.3},
                attrs={"sibling_rows_batched": 2, "sibling_rows_single": 8})
    obs = obs_of([
        get_root(duration_s=0.3, stages={"volume.read.shard": 0.25}),
        get_root(duration_s=0.7, children=[recon]),
        doc("rpc.ec_shard_read", 0.05),
    ])
    for name in PEERS:
        value = reader(name)(obs, None)
        assert (value == pytest.approx(50.0)) if name == "peer_serve_ms_per_read" else value is None
    for name in PEERS:  # a window with no GET and no stream at all
        assert reader(name)(obs_of([]), None) is None


def test_the_readers_on_documents_kept_from_a_run():
    """`spread.span_docs.json`: three GETs of the cluster of
    tests/test_ec_spread_reads.py, armed (8 MiB, CPU): a needle with
    one interval on a peer; a needle on lost shard 1, reconstructed at
    server 4 from its own two rows and eight of ten fetches; the same
    again, from that server's interval cache. With the holders' roots."""
    docs = json.loads((HERE / "spread.span_docs.json").read_text())
    obs = obs_of(docs)
    gets = [d for d in docs if d["op"] == "http.volume"]
    served = [d for d in docs if d["op"] == "rpc.ec_shard_read"]
    assert len(gets) == 3 and len(served) == 11
    (recon,) = [c for g in gets for c in g["children"] if c["stages"]]
    waited = gets[0]["stages"]["peer_read"]["seconds"] + recon["stages"]["peer_read"]["seconds"]
    assert reader("peer_read_ms_per_get")(obs, None) == pytest.approx(1e3 * waited / 3)
    assert reader("peer_reads_per_get")(obs, None) == pytest.approx(11 / 3)
    assert reader("remote_sibling_share")(obs, None) == pytest.approx(80.0)
    assert reader("peer_fetch_unused_share")(obs, None) == pytest.approx(20.0)
    assert reader("peer_serve_ms_per_read")(obs, None) == pytest.approx(
        1e3 * sum(d["duration_s"] for d in served) / 11
    )
    # both sides saw the same reads and the same bytes
    assert gets[0]["attrs"]["peer_read_bytes"] == served[0]["attrs"]["size"] == 1192


def test_the_kept_documents_give_the_same_values_through_metrics(manifest):
    """What a traced run's result line carries: the harness's walk over
    the entries that list the cell finds the five readers' files and
    their hand-worked values, with the entries' units."""
    docs = json.loads((HERE / "spread.span_docs.json").read_text())
    obs = harness.Observed()
    obs.spans = docs
    obs.counters["compiles_in_window"] = 0
    metrics = harness.read_layers(harness.resolve_cell(manifest, CELL, 1, 20.0, True), obs)
    gets = [d for d in docs if d["op"] == "http.volume"]
    served = [d["duration_s"] for d in docs if d["op"] == "rpc.ec_shard_read"]
    (recon,) = [c for g in gets for c in g["children"] if c["stages"]]
    waited = gets[0]["stages"]["peer_read"]["seconds"] + recon["stages"]["peer_read"]["seconds"]
    by_hand = {
        "peer_read_ms_per_get": 1e3 * waited / 3, "peer_reads_per_get": 11 / 3,
        "peer_serve_ms_per_read": 1e3 * sum(served) / 11,
        "remote_sibling_share": 80.0, "peer_fetch_unused_share": 20.0,
    }
    for name, want in by_hand.items():
        assert metrics[name] == {"value": pytest.approx(want), "unit": PEERS[name][0]}, name
    # the metrics that were `ycsb-c`'s alone read these documents too:
    # one GET of three reconstructed, two held an `ec.degraded_read`
    assert metrics["reconstructing_get_share"]["value"] == pytest.approx(100 / 3)
    assert metrics["healthy_get_p50_ms"]["value"] == pytest.approx(1e3 * gets[0]["duration_s"])
    assert metrics["singleflight_wait_share"]["value"] == 0.0
    # the seam stamps are on the kept spans, twelve returns in three GETs;
    # no `interp.probe` root was kept: its four readers say nothing
    assert metrics["interp_returns_per_get"]["value"] == pytest.approx(4.0)
    assert set(PROBES) & set(metrics) == {"interp_wait_ms_per_get", "interp_returns_per_get"}
    assert recon["attrs"]["peer_fetches_started"] == len(served) - gets[0]["attrs"]["peer_reads"]
    assert "sibling_read" in recon["stages"] and "peer_read" in recon["stages"]


# ----------------------------------------------------------- the rehearsals


def run(manifest, traced=False, seconds=2.0, **kw):
    out = io.StringIO()
    result = harness.run_cell(
        manifest, CELL, 2**31 + 35, seconds, traced, require_tpu=False,
        overrides=SMALL, out=out, **kw,
    )
    assert json.loads(out.getvalue().splitlines()[-1]) == result
    return result


def test_a_traced_rehearsal_is_correct_and_reports_the_shared_and_the_peers_metrics(
    manifest, capsys
):
    result = run(manifest, traced=True, seconds=3.5)
    assert "ecbench: peer shard reads" not in capsys.readouterr().err  # once, in `metrics`
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    for name in ("no_peer_read", "entry_servers_unused", "shards_on_entry_server_only",
                 "no_healthy_get", "gets_wrong", "gets_failed", "fallback_batches"):
        assert compared[name] == {"value": 0, "limit": 0}, name
    served = {
        name: compared[name]["value"]
        for name in ("peer_bytes_served", "peer_bytes_served_stream", "peer_bytes_served_plane")
    }
    assert all(compared[name]["limit"] is None for name in served)
    assert served["peer_bytes_served"] > 0
    assert served["peer_bytes_served"] == (
        served["peer_bytes_served_stream"] + served["peer_bytes_served_plane"]
    )
    # this program's readers know the stream alone
    assert served["peer_bytes_served_plane"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(SHARED) - {"rs_device_ms_per_get", "rs_glue_share_of_device",
                          "get_idle_unattributed_share"} <= set(metrics)  # no device trace here
    # every newly listed metric comes with a number: none is listed to say nothing
    for name in tuple(PEERS) + PROBES + YCSB:
        assert isinstance(metrics[name], float) and metrics[name] >= 0, name
    # two rows of a reconstruction are this server's own, eight its peers'
    assert metrics["sibling_batched_share"] == pytest.approx(20.0, abs=5.0)
    assert metrics["remote_sibling_share"] == pytest.approx(80.0, abs=5.0)
    # ten shards have a holder, eight rows are needed
    assert 0 <= metrics["peer_fetch_unused_share"] <= 20.0 + 1e-4
    assert metrics["peer_reads_per_get"] > 0.5 and metrics["peer_read_ms_per_get"] > 0
    assert 0 < metrics["peer_serve_ms_per_read"]
    assert metrics["sibling_read_ms_per_get"] > 0  # the rows that lie here
    assert 0 < metrics["reconstructing_get_share"] < 100 and metrics["healthy_get_p50_ms"] > 0
    assert metrics["cores_busy"] > 0.1
    # both sides count the same bytes, whatever the plane, but for streams
    # that the window's end found running
    readers_bytes = compared["peer_reader_bytes"]["value"]
    assert abs(readers_bytes - served["peer_bytes_served"]) <= 0.02 * readers_bytes
    from seaweedfs_tpu.utils import trace

    trace.configure(enabled=False)


@pytest.fixture
def small_cluster(manifest, driver):
    """The cell's own set-up at 8 MiB: seven servers, server 1 stopped,
    swept and warmed; the driver's state, stopped and removed after."""
    cell = harness.resolve_cell(manifest, CELL, 2**31 + 36, 1.0, False, overrides=SMALL)
    cell.started = time.perf_counter()
    shutil.rmtree(harness.DATA_DIR, ignore_errors=True)
    os.makedirs(cell.data_dir)
    st = None
    try:
        st = driver.setup(cell)
        yield cell, st
    finally:
        if st is not None:
            driver.teardown(st)
        shutil.rmtree(harness.DATA_DIR, ignore_errors=True)


def by_name(compared):
    return {c.name: c for c in compared}


def test_a_byte_served_on_the_native_shard_plane_counts_and_an_empty_window_does_not(
    driver, small_cluster
):
    """The case the rehearsal cannot reach while the program's reader
    knows only the stream: one `NetPlaneClient.read_into` of a live
    peer's range inside a window of the driver's counting."""
    from seaweedfs_tpu.ec.net_plane import NetPlaneClient
    from seaweedfs_tpu.utils import metrics

    _cell, st = small_cluster
    holder = next(s for s in st.live if st.cluster.servers[s].net_plane is not None)
    plane = st.cluster.servers[holder].net_plane
    ev = st.evs[st.live.index(holder)]
    sid = sorted(ev.shard_ids)[0]
    size = min(4096 + 123, os.fstat(ev.shard_fds[sid]).st_size - 512)
    want = os.pread(ev.shard_fds[sid], size, 512)
    # the warm-up's reconstructions left answers unread: let the holders'
    # streams run out before the counting opens
    quiet_since, last = time.monotonic(), driver._python_plane_sent()
    while time.monotonic() - quiet_since < 0.5:
        time.sleep(0.05)
        if driver._python_plane_sent() != last:
            quiet_since, last = time.monotonic(), driver._python_plane_sent()
    readers0 = driver._peer_reader_bytes()
    native0 = metrics.net_bytes_sent_total.snapshot().get(("native", "read"), 0)

    nobody = driver.ServedMeter(st).read(0)
    assert nobody == {"peer_bytes_served_stream": 0, "peer_bytes_served_plane": 0,
                      "peer_bytes_served": 0}
    empty = by_name(driver.served_compared(nobody))
    assert (empty["no_peer_read"].value, empty["no_peer_read"].limit) == (1, 0)
    assert not empty["no_peer_read"].ok  # a window in which nobody serves is not correct

    meter = driver.ServedMeter(st)
    client = NetPlaneClient()
    try:
        dst = np.zeros(size, np.uint8)
        client.read_into((plane.ip, plane.port), st.volume.vid, sid, ev.encode_ts_ns, 512,
                         size, dst)
    finally:
        client.close()
    assert dst.tobytes() == want  # the holder's bytes, generation-fenced as the stream's
    counted = meter.read(0)
    assert counted == {"peer_bytes_served_stream": 0, "peer_bytes_served_plane": size,
                       "peer_bytes_served": size}
    compared = by_name(driver.served_compared(counted))
    assert compared["no_peer_read"].value == 0 and compared["no_peer_read"].ok
    assert all(compared[n].limit is None and compared[n].value == counted[n] for n in counted)
    # the program's reader took no part, and the process's counter of the
    # native plane is not what was read: GET bodies are booked there too
    assert driver._peer_reader_bytes() == readers0
    assert metrics.net_bytes_sent_total.snapshot().get(("native", "read"), 0) - native0 in (0, size)


def test_a_program_or_a_server_without_a_plane_reads_nought_there(driver):
    """Three live servers: one with a plane that has sent on both of its
    egresses, one whose plane's port was taken, one of a program that
    never had a plane."""
    plane = types.SimpleNamespace(sendfile_bytes=700, python_bytes=30)
    servers = [types.SimpleNamespace(net_plane=plane), "stopped",
               types.SimpleNamespace(net_plane=None), types.SimpleNamespace()]
    st = types.SimpleNamespace(cluster=types.SimpleNamespace(servers=servers), live=[0, 2, 3])
    assert driver._shard_planes_sent(st) == (700, 30)
    meter = driver.ServedMeter(st)
    plane.sendfile_bytes += 5
    got = meter.read(0)
    assert got["peer_bytes_served_plane"] == 5
    assert got["peer_bytes_served"] == got["peer_bytes_served_stream"] + 5
    st.live = [2, 3]
    assert driver._shard_planes_sent(st) == (0, 0)
    assert driver.ServedMeter(st).read(0)["peer_bytes_served_plane"] == 0


def test_the_control_comes_out_as_not_correct(manifest):
    result = run(manifest, control=True)
    assert result["correct"] is False
    assert result["compared"]["gets_wrong"]["value"] > 0
    assert result["compared"]["no_peer_read"]["value"] == 0


def test_a_reconstruction_altered_where_it_is_produced_gives_no_result(manifest, monkeypatch):
    """The device backend hands back a row with one byte changed: what
    was reconstructed fails the `.ecsum` check, the GET is refused, and
    the warm-up's sweep ends the run in the server's own words."""
    from ecbench.cluster import BenchError
    from seaweedfs_tpu.ec import backend as B

    real_to_host = B.JaxBackend.to_host

    def bent_to_host(self, arr):
        out = np.array(real_to_host(self, arr))
        out[0, 0] ^= 1
        return out

    monkeypatch.setattr(B.JaxBackend, "to_host", bent_to_host)
    with pytest.raises(BenchError, match="fails sidecar verification"):
        run(manifest)


def test_two_servers_that_answer_from_the_wrong_shard_are_seen_as_not_correct(
    manifest, driver, monkeypatch
):
    """From the window's start servers 2 and 3 read each of their two
    shards from the OTHER one's file, for their own GETs and for their
    peers' `VolumeEcShardRead`s alike. One such server the program heals
    around (every interval fails its needle's CRC or its row's `.ecsum`
    check, and ten good shards are left); with two, eight are left and
    no matrix can be filled: GETs are refused, never answered with a
    wrong body, and the run is not correct."""
    real_window = driver.window

    def window(cell, st, slice_):
        for s in (2, 3):
            ev = st.cluster.servers[s].store.find_ec_volume(st.volume.vid)
            a, b = cell.config["placement"]["shards_of_server"][s]
            ev.shard_fds[a], ev.shard_fds[b] = ev.shard_fds[b], ev.shard_fds[a]
        return real_window(cell, st, slice_)

    monkeypatch.setattr(driver, "window", window)
    result = run(manifest)
    assert result["correct"] is False
    assert result["compared"]["gets_wrong"]["value"] > 0
    assert result["failed"] == result["compared"]["gets_wrong"]["value"]
