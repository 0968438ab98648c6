"""The cell `vol1g-10p4-7vs-node-down.ycsb-c-spread`: its configuration
against the one it shares a volume with, its entries in BENCHMARK.json,
its entry-server draws, its five readers on kept and hand-worked span
documents, and the cell rehearsed at 8 MiB on the CPU with its control
and two planted faults."""

import io
import json
import pathlib
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
# what the spread adds to the program's records: readers that a traced
# run of this cell prints on standard error, and BENCHMARK.json does not
# list (`http_gets_ycsb_spread.PEER_READERS` says why)
PEERS = (
    "peer_read_ms_per_get", "peer_reads_per_get", "peer_serve_ms_per_read",
    "remote_sibling_share", "peer_fetch_unused_share",
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


def test_the_cell_is_listed_where_its_readers_find_something_and_nowhere_pinned(manifest, driver):
    listed = {
        m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]
        if CELL in m.get("workloads", ())
    }
    assert listed == set(SHARED) | {"fg_p50_ms", "fg_p95_ms", "fg_ops_per_s"}
    for m in manifest["per_layer"]:
        if m["name"] in SHARED:
            assert m["workloads"][-1] == CELL and CONTROL in m["workloads"]
    assert manifest["configs"][-1]["name"] == CELL.split(".")[0]
    assert manifest["workloads"][-1]["name"] == CELL
    # the readers of the peers' reads are files that wait for their entries
    assert driver.PEER_READERS == PEERS
    assert not set(PEERS) & {m["name"] for m in manifest["per_layer"]}
    for name in PEERS:
        assert callable(reader(name))


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
    (line,) = [ln for ln in capsys.readouterr().err.splitlines()
               if ln.startswith("ecbench: peer shard reads: ")]
    peers = {k: float(v) for k, v in (kv.split("=") for kv in line.split(": ")[2].split())}
    assert result["correct"] is True and result["failed"] == 0
    compared = result["compared"]
    for name in ("no_peer_read", "entry_servers_unused", "shards_on_entry_server_only",
                 "no_healthy_get", "gets_wrong", "gets_failed", "fallback_batches"):
        assert compared[name] == {"value": 0, "limit": 0}, name
    assert compared["peer_bytes_served"]["value"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(SHARED) - {"rs_device_ms_per_get", "rs_glue_share_of_device",
                          "get_idle_unattributed_share"} <= set(metrics)  # no device trace here
    assert set(peers) == set(PEERS) and not set(PEERS) & set(metrics)
    # two rows of a reconstruction are this server's own, eight its peers'
    assert metrics["sibling_batched_share"] == pytest.approx(20.0, abs=5.0)
    assert peers["remote_sibling_share"] == pytest.approx(80.0, abs=5.0)
    # ten shards have a holder, eight rows are needed
    assert 0 <= peers["peer_fetch_unused_share"] <= 20.0 + 1e-4
    assert peers["peer_reads_per_get"] > 0.5 and peers["peer_read_ms_per_get"] > 0
    assert 0 < peers["peer_serve_ms_per_read"]
    assert metrics["sibling_read_ms_per_get"] > 0  # the rows that lie here
    # both sides count the same bytes, but for streams that the window's
    # end found running
    readers_bytes = compared["peer_reader_bytes"]["value"]
    assert abs(readers_bytes - compared["peer_bytes_served"]["value"]) <= 0.02 * readers_bytes
    from seaweedfs_tpu.utils import trace

    trace.configure(enabled=False)


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
