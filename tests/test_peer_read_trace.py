"""One read from a peer, one trace, both sides (ISSUE 38): the reader's
`ec.peer_read` span with its four stages end to end, the holder's
`rpc.ec_shard_read` joined to it by id and split into parts, the stamped
returns of `recv_into` and `send_file`, a fetch that outlives its
reconstruction, `addr` on a GET's root and `matrix_regathers`.

Two volume servers in this process (`ecbench/spread_cluster.py`, 8 MiB,
CPU): server 0 holds shards 1-6 (shard 0 is lost: unmounted and listed
nowhere), server 1 holds 7-13. A needle off shard 0 with bytes on shard
7 is a healthy GET that reads an interval from a peer; a needle on shard
0 is reconstructed at server 0 from its own six rows and four of the
seven it asks of server 1.
"""

from __future__ import annotations

import http.client
import json
import os
import socket
import time

import numpy as np
import pytest

from ecbench import data as D
from ecbench import harness
from ecbench.spread_cluster import SpreadCluster
from seaweedfs_tpu.ec import net_plane
from seaweedfs_tpu.ec.placement import node_view_for
from seaweedfs_tpu.pb import cluster_pb2 as pb
from seaweedfs_tpu.utils import native, trace

PLAN = {
    "large_body_bytes": 1 << 20, "small_per_gib": 300,
    "small_min_bytes": 1024, "small_max_bytes": 65536, "layout_seed": 24,
}
LAYOUT = {"data_shards": 10, "parity_shards": 4,
          "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}
SEED = 2**31 + 38
LOST = 0
PLACED = [[1, 2, 3, 4, 5, 6], [7, 8, 9, 10, 11, 12, 13]]
READ_STAGES = ("fetch_queue", "conn_checkout", "request_rtt", "payload_land")


class Pair:
    def __init__(self, cl, vol):
        self.cl, self.vol = cl, vol
        gets = harness.load_module("drivers", "http_gets")
        on = lambda sid: set(gets.needles_on_shard(vol, sid, LAYOUT))
        self.on_lost = sorted(on(LOST))
        # healthy, with an interval on the peer's shard 7
        self.on_peer = sorted(on(7) - on(LOST))
        assert self.on_lost and self.on_peer

    def ev(self, s):
        return self.cl.servers[s].store.find_ec_volume(self.vol.vid)

    def plane(self, s):
        return self.cl.servers[s].net_plane

    def label(self, s):
        return f"localhost:{self.cl.servers[s].port}"

    def get(self, s, i):
        conn = http.client.HTTPConnection(*self.cl.host(s), timeout=30)
        try:
            conn.request("GET", f"/{self.vol.fid(i)}")
            resp = conn.getresponse()
            body = resp.read()
        finally:
            conn.close()
        assert resp.status == 200 and body == self.vol.body(i)

    def drop_caches(self):
        for s in (0, 1):
            cache = self.ev(s).interval_cache
            if cache is not None:
                cache.drop_prefix("")

    def quiet(self):
        """Until no plane serves a request any more: an earlier
        reconstruction's unread fetches have ended."""
        last, still = -1, 0
        deadline = time.time() + 10
        while still < 3 and time.time() < deadline:
            now = sum(self.plane(s).sendfile_bytes + self.plane(s).python_bytes for s in (0, 1))
            still = still + 1 if now == last else 0
            last = now
            time.sleep(0.05)

    def root(self, i):
        """The newest `http.volume` root of a GET of needle `i` (a root
        lands after its response has left)."""
        deadline = time.time() + 10
        while True:
            found = [d for d in trace.traces()
                     if d["op"] == "http.volume" and d["name"] == f"GET /{self.vol.fid(i)}"]
            if found or time.time() > deadline:
                return found[-1]
            time.sleep(0.02)

    def served(self, root, want):
        """The holders' `rpc.ec_shard_read` roots of a GET's trace, once
        `want` of them have landed (a holder's span closes after the
        reader has its bytes)."""
        deadline = time.time() + 10
        while True:
            found = [d for d in trace.traces(trace_id=root["trace_id"])
                     if d["op"] == "rpc.ec_shard_read"]
            if len(found) >= want or time.time() > deadline:
                return found
            time.sleep(0.02)


@pytest.fixture(scope="module")
def pair(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("pair"))
    vol_dir = os.path.join(root, "vs0")
    os.makedirs(vol_dir)
    vol = D.fabricate_volume(vol_dir, 1, SEED, 8 << 20, PLAN)
    cl = SpreadCluster(root, {"ec_backend": "cpu", "ec_interval_cache_mb": 1}, False, servers=2)
    try:
        cl.wait_volume_listed(vol.vid)
        cl.shell(f"ec.encode -volumeId {vol.vid}")
        cl.move_shards(vol.vid, 0, 1, PLACED[1])
        cl.stubs[0].VolumeEcShardsUnmount(
            pb.EcShardsUnmountRequest(volume_id=vol.vid, shard_ids=[LOST]), timeout=30
        )
        cl.wait_placement(vol.vid, PLACED)
        yield Pair(cl, vol)
    finally:
        trace.configure(enabled=False)
        cl.stop()


@pytest.fixture
def armed(pair, disarmed):
    pair.quiet()
    pair.drop_caches()
    trace.configure(enabled=True, ring_size=4096)
    trace.reset()
    yield pair
    pair.quiet()


def walk(doc):
    yield doc
    for c in doc["children"]:
        yield from walk(c)


def peer_reads(doc):
    return [d for d in walk(doc) if d["op"] == "ec.peer_read"]


def end_to_end(doc, names):
    """The span's stage intervals are of `names`, lie end to end from the
    span's start to its end, and add up to it."""
    ivs = sorted(doc["intervals"], key=lambda iv: (iv[1], iv[2]))
    assert ivs and {iv[0] for iv in ivs} <= set(names), doc["stages"]
    assert ivs[0][1] == doc["start_ns"] and ivs[-1][2] == doc["end_ns"]
    for a, b in zip(ivs, ivs[1:]):
        assert a[2] == b[1], (a, b)
    total = sum(acc["seconds"] for acc in doc["stages"].values())
    assert total == pytest.approx(doc["duration_s"], rel=0.05)
    order = [n for n in names if n in doc["stages"]]
    firsts = [min(iv[1] for iv in ivs if iv[0] == n) for n in order]
    assert firsts == sorted(firsts)  # each stage first entered in the order named


class holder_plane:
    """How server 1's shard plane answers server 0 for the with-block:
    `native` as it stands; `stream`: the reader takes the plane for
    absent (the client's memo of a refused connect) and asks the
    `VolumeEcShardRead` stream; `refused`: the plane answers every
    request with a refusal, and the reader asks the stream after it."""

    def __init__(self, pair, how):
        self.pair, self.how = pair, how

    def __enter__(self):
        p = self.pair
        self.client = p.cl.servers[0]._net_plane_client()
        self.addr = net_plane.net_addr(p.cl.grpc_addr(1))
        if self.how == "stream":
            with self.client._lock:
                self.client._no_plane[self.addr] = time.monotonic()
        elif self.how == "refused":
            def refuse(vid, sid, gen):
                raise net_plane.NetPlaneError("not today")

            p.plane(1).resolve, self.real = refuse, p.plane(1).resolve

    def __exit__(self, *exc):
        if self.how == "stream":
            self.client.reset(self.addr)
        elif self.how == "refused":
            self.pair.plane(1).resolve = self.real


# --------------------------------------------------- the reader's span


@pytest.mark.parametrize("how", ["native", "stream", "refused"])
def test_an_interval_from_a_peer_is_a_child_of_the_root_with_stages_end_to_end(armed, how):
    p, i = armed, armed.on_peer[0]
    with holder_plane(p, how):
        p.get(0, i)
    root = p.root(i)
    reads = peer_reads(root)
    assert reads and reads == [c for c in root["children"] if c["op"] == "ec.peer_read"]
    assert len(reads) == root["attrs"]["peer_reads"]
    plane = "native" if how == "native" else "stream"
    for r in reads:
        a = r["attrs"]
        assert r["parent_span_id"] == root["span_id"] and r["trace_id"] == root["trace_id"]
        assert (a["kind"], a["answered"], a["plane"]) == ("interval", 1, plane)
        assert a["shard"] in PLACED[1] and a["size"] > 0 and "unused" not in a
        assert a["peer"] == p.cl.grpc_addr(1)
        # no pool between the worker and its read
        end_to_end(r, READ_STAGES[1:])
        assert "request_rtt" in r["stages"] and "payload_land" in r["stages"]
        assert r["thread"] == root["thread"]
        # the root's `peer_read` stage encloses the child
        (outer,) = [iv for iv in root["intervals"] if iv[0] == "peer_read"
                    and iv[1] <= r["start_ns"] and r["end_ns"] <= iv[2]]
        assert outer
    if how == "refused":
        # asked twice, the plane and then the stream, in ONE `request_rtt`
        # (a turn to the stage that is open is nothing); both of the
        # holder's roots name the read
        served = p.served(root, 2 * len(reads))
        for r in reads:
            assert r["stages"]["request_rtt"]["count"] == 1
            mine = [h for h in served if h["parent_span_id"] == r["span_id"]]
            assert sorted(h["attrs"].get("plane", "") for h in mine) == ["", "native"]


def test_a_reconstructions_rows_are_children_of_its_span_and_begin_at_their_submit(armed):
    p, i = armed, armed.on_lost[0]
    p.get(0, i)
    root = p.root(i)
    (recon,) = [d for d in walk(root) if d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"]]
    reads = [c for c in recon["children"] if c["op"] == "ec.peer_read"]
    a = recon["attrs"]
    # (a fetch that found the matrix full before a thread took it up was
    # cancelled, and is neither started nor a span)
    assert 4 <= len(reads) == a["peer_fetches_started"] <= len(PLACED[1])
    assert {r["attrs"]["shard"] for r in reads} <= set(PLACED[1])
    waits = [iv for iv in recon["intervals"] if iv[0] == "peer_read"]
    for r in reads:
        assert r["attrs"]["kind"] == "sibling" and r["thread"].startswith("ec-peer-fetch")
        assert r["parent_span_id"] == recon["span_id"]
        assert recon["start_ns"] <= r["start_ns"] and r["end_ns"] <= recon["end_ns"]
        if r["attrs"].get("unused"):
            continue
        assert r["attrs"]["answered"] == 1 and r["attrs"]["plane"] == "native"
        end_to_end(r, READ_STAGES)
        # the queue begins inside the parent's first `peer_read` wait,
        # which is the submits, and ends in the fetch's own thread
        (queued,) = [iv for iv in r["intervals"] if iv[0] == "fetch_queue"]
        assert waits[0][1] <= queued[1] <= waits[0][2] and queued[3].startswith("ec-peer-fetch")
    answered = [r for r in reads if r["attrs"]["answered"] == 1]
    assert len(answered) >= a["sibling_rows_remote"] == 4
    assert a.get("peer_reads_outlived", 0) == sum(1 for r in reads if r["attrs"].get("unused"))


# ------------------------------------------------------ the holder's span


@pytest.mark.parametrize("how", ["native", "stream"])
def test_every_answered_read_has_one_holder_root_that_names_it_as_parent(armed, how):
    p = armed
    with holder_plane(p, how):
        p.get(0, p.on_peer[0])
        p.get(0, p.on_lost[0])
        roots = [p.root(p.on_peer[0]), p.root(p.on_lost[0])]
        reads = [r for root in roots for r in peer_reads(root)]
        served = [h for root in roots for h in p.served(root, len(peer_reads(root)))]
    answered = [r for r in reads if r["attrs"]["answered"] == 1 and not r["attrs"].get("unused")]
    assert len(answered) >= 5 and {r["attrs"]["kind"] for r in answered} == {"interval", "sibling"}
    by_parent: dict[str, list] = {}
    for h in served:
        by_parent.setdefault(h["parent_span_id"], []).append(h)
    for r in answered:
        (h,) = by_parent[r["span_id"]]  # exactly one
        assert h["trace_id"] == r["trace_id"] and h["server"] == p.label(1)
        assert (h["attrs"]["shard"], h["attrs"]["size"]) == (r["attrs"]["shard"], r["attrs"]["size"])
        assert h["attrs"].get("plane") == ("native" if how == "native" else None)
        assert r["attrs"]["plane"] == how
        # one process, one clock: the holder's span opens inside the read
        # (it may close after it: the reader has its bytes before the
        # holder's thread holds the interpreter again)
        assert r["start_ns"] <= h["start_ns"] <= r["end_ns"]
    # and no holder's root is an orphan: each names a read of these GETs
    assert set(by_parent) <= {r["span_id"] for r in reads}


@pytest.mark.parametrize("how,parts", [
    ("native", ("stream.resolve", "stream.header", "stream.sendfile")),
    ("stream", ("stream.resolve", "stream.sendfile")),
])
def test_the_holders_stream_is_split_into_parts_that_lie_inside_it(armed, how, parts):
    p, i = armed, armed.on_peer[0]
    with holder_plane(p, how):
        p.get(0, i)
        root = p.root(i)
        served = p.served(root, len(peer_reads(root)))
    assert served
    for h in served:
        assert {s for s in h["stages"] if s != "stream"} == set(parts)
        (whole,) = [iv for iv in h["intervals"] if iv[0] == "stream"]
        inner = sorted((iv for iv in h["intervals"] if iv[0] != "stream"), key=lambda iv: iv[1])
        assert [iv[0] for iv in inner] == list(parts)
        assert whole[1] <= inner[0][1] and inner[-1][2] <= whole[2]
        for a, b in zip(inner, inner[1:]):
            assert a[2] == b[1]
        assert sum(h["stages"][s]["seconds"] for s in parts) <= h["stages"]["stream"]["seconds"] + 1e-9
        # the local root and its one stage are what they were
        assert h["stages"]["stream"]["count"] == 1
        assert h["stages"]["stream"]["seconds"] <= h["duration_s"]


# ------------------------------------------------------ the stamped seams


def test_armed_the_two_plane_seams_book_one_return_each_to_the_span_at_hand(armed):
    p, i = armed, armed.on_peer[0]
    p.get(0, i)
    root = p.root(i)
    reads = peer_reads(root)
    for r in reads:  # `sn_recv_into`, to the reader's span
        assert r["attrs"]["interp_returns"] == 1 and r["attrs"]["interp_wait_ns"] >= 0
    for h in p.served(root, len(reads)):  # `sn_send_file`, to the holder's
        assert h["attrs"]["interp_returns"] == 1 and h["attrs"]["interp_wait_ns"] >= 0


def test_recv_into_and_send_file_book_to_the_open_stages_span_else_the_ambient(armed, tmp_path):
    path = tmp_path / "blob"
    path.write_bytes(b"x" * 4096)
    outer, inner = trace.Span("ec.outer"), trace.Span("ec.inner")
    a, b = socket.socketpair()
    fd = os.open(path, os.O_RDONLY)
    try:
        with trace.activate(outer):
            assert native.send_file(a.fileno(), fd, 0, 4096) == 4096  # ambient
            with trace.stage(inner, "payload_land"):
                dst = np.empty(4096, np.uint8)
                assert native.recv_into(b.fileno(), dst, 4096) == 4096  # the stage's span
        assert bytes(dst) == b"x" * 4096
        assert outer.attrs["interp_returns"] == 1 and inner.attrs["interp_returns"] == 1
        assert outer.attrs["interp_wait_ns"] >= 0 and inner.attrs["interp_wait_ns"] >= 0
        # with no span at hand the stamp goes nowhere, and nothing raises
        assert native.send_file(a.fileno(), fd, 0, 16) == 16
        assert native.recv_into(b.fileno(), dst, 16) == 16
    finally:
        os.close(fd)
        a.close()
        b.close()


def test_disarmed_the_seams_get_null_and_a_get_makes_no_span(pair, disarmed, monkeypatch):
    p = pair
    p.quiet()
    p.drop_caches()
    stamps: dict[str, list] = {"sn_send_file": [], "sn_recv_into": []}
    for name in stamps:
        real = getattr(native._lib, name)
        monkeypatch.setattr(
            native._lib, name,
            lambda *args, _real=real, _name=name: (stamps[_name].append(args[-1]), _real(*args))[1],
        )
    made = []
    init = trace.Span.__init__
    monkeypatch.setattr(
        trace.Span, "__init__", lambda self, *a, **kw: (made.append(a[:1]), init(self, *a, **kw))[1]
    )
    p.get(0, p.on_peer[0])
    p.get(0, p.on_lost[0])
    p.quiet()
    assert len(stamps["sn_recv_into"]) >= 5 and len(stamps["sn_send_file"]) >= 5
    assert set(stamps["sn_recv_into"]) == {None} and set(stamps["sn_send_file"]) == {None}
    assert made == [] and trace.traces() == []
    # armed, each call gets a word to stamp
    trace.configure(enabled=True)
    for name in stamps:
        stamps[name].clear()
    p.drop_caches()
    p.get(0, p.on_peer[0])
    assert stamps["sn_recv_into"] and all(isinstance(w, int) and w for w in stamps["sn_recv_into"])
    assert stamps["sn_send_file"] and all(isinstance(w, int) and w for w in stamps["sn_send_file"])
    assert made


# ------------------------------------- an answer that nobody waits for


class slow_shards:
    """Server 1's plane takes `seconds` longer to look these shards up."""

    def __init__(self, pair, shards, seconds=1.0):
        self.plane, self.shards, self.seconds = pair.plane(1), set(shards), seconds

    def __enter__(self):
        real = self.real = self.plane.resolve

        def resolve(vid, sid, gen):
            if sid in self.shards:
                time.sleep(self.seconds)
            return real(vid, sid, gen)

        self.plane.resolve = resolve

    def __exit__(self, *exc):
        self.plane.resolve = self.real


def test_an_unread_answer_closes_unused_and_leaves_its_parents_document_well_formed(armed):
    p, i = armed, armed.on_lost[0]
    with slow_shards(p, [13]):
        p.get(0, i)
        root = p.root(i)
        p.quiet()  # the slow fetch ends, long after its span was closed
    (recon,) = [d for d in walk(root) if d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"]]
    unused = [c for c in recon["children"] if c["attrs"].get("unused")]
    # (under load another fetch may be late too)
    assert recon["attrs"]["peer_reads_outlived"] == len(unused) >= 1
    (late,) = [c for c in unused if c["attrs"]["shard"] == 13]
    a = late["attrs"]
    assert (a["shard"], a["unused"], a["answered"], a["kind"]) == (13, 1, 0, "sibling")
    # it has the length it had when its parent closed, and nothing after
    assert late["end_ns"] == recon["end_ns"] and late["start_ns"] >= recon["start_ns"]
    assert late["duration_s"] < 0.9  # not the second its holder slept
    assert set(late["stages"]) <= set(READ_STAGES)
    assert all(late["start_ns"] <= iv[1] <= iv[2] <= late["end_ns"] for iv in late["intervals"])
    assert sum(s["seconds"] for s in late["stages"].values()) <= late["duration_s"] + 1e-9
    # the whole tree: every span inside its parent, every document whole
    for d in walk(root):
        for c in d["children"]:
            assert d["start_ns"] <= c["start_ns"] and c["end_ns"] <= d["end_ns"], (d["op"], c["op"])
            assert c["parent_span_id"] == d["span_id"]
    json.dumps(root)
    # the readers of the layer leave it out
    reader = harness.load_module("layers", "peer_request_ms_per_read")
    obs = type("Obs", (), {"spans": [root]})
    assert late["span_id"] not in {r["span_id"] for r in reader.reads(obs)}
    assert len(reader.reads(obs)) == len(peer_reads(root)) - len(unused)


@pytest.mark.parametrize("slow,regathers", [
    ((11, 12, 13), 0),  # the four open rows' own fetches come first
    ((7,), 1),          # a spare stands in for the first open row
], ids=["rows_land_where_they_are_used", "a_spare_stands_in"])
def test_matrix_regathers_counts_the_gathered_copy(armed, slow, regathers):
    p, i = armed, armed.on_lost[0]
    with slow_shards(p, slow, 0.6):
        p.get(0, i)
        root = p.root(i)
        p.quiet()
    (recon,) = [d for d in walk(root) if d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"]]
    a = recon["attrs"]
    assert a["matrix_regathers"] == regathers and a["sibling_rows_remote"] == 4
    assert a["peer_reads_outlived"] >= (1 if regathers else 0)
    reader = harness.load_module("layers", "matrix_regather_share")
    assert reader.read(type("Obs", (), {"spans": [root]}), None) == 100.0 * regathers


# --------------------------------------------------- which server answered


@pytest.mark.parametrize("entry", [0, 1])
def test_a_gets_root_says_which_server_answered(armed, entry):
    p = armed
    i = p.on_peer[0]
    p.get(entry, i)
    root = p.root(i)
    assert root["attrs"]["addr"] == p.label(entry) and root["server"] == "volume"
    # the other side of its reads from peers says the other server
    for h in p.served(root, len(peer_reads(root))):
        assert h["server"] == p.label(1 - entry)


# --------------------------------------- what the tracer gained for this


@pytest.fixture
def recorder(disarmed):
    trace.configure(enabled=True, ring_size=256)
    trace.reset()
    yield trace


def test_turn_ends_the_open_stage_and_begins_the_next_at_one_clock_reading(recorder):
    sp = trace.start("ec.peer_read")
    with sp.stage("conn_checkout") as timer:
        began = timer.began_ns
        trace.turn("conn_checkout")  # open already: nothing
        trace.turn("request_rtt")
        time.sleep(0.002)
        trace.turn("payload_land")
        trace.turn("no_such_stage")  # not a stage that turns: nothing
    sp.finish(timer.ended_ns)
    doc = trace.traces()[-1]
    assert [iv[0] for iv in doc["intervals"]] == list(READ_STAGES[1:])
    assert all(acc["count"] == 1 for acc in doc["stages"].values())
    assert doc["intervals"][0][1] == began and doc["end_ns"] == doc["intervals"][-1][2]
    for a, b in zip(doc["intervals"], doc["intervals"][1:]):
        assert a[2] == b[1]
    assert doc["stages"]["request_rtt"]["seconds"] >= 0.002


def test_turn_leaves_any_other_open_stage_alone_and_is_nothing_disarmed(recorder):
    sp = trace.start("ec.peer_rebuild")
    with sp.stage("peer_fetch"):
        trace.turn("request_rtt")  # a rebuild's fetch makes the same client calls
    assert trace.turn("request_rtt") is None  # no stage open
    sp.finish()
    assert set(trace.traces()[-1]["stages"]) == {"peer_fetch"}
    trace.configure(enabled=False)
    with trace.stage(None, "conn_checkout"):
        assert trace.turn("request_rtt") is None


def test_the_first_close_of_a_span_holds_with_its_attributes(recorder):
    parent = trace.start("ec.degraded_read")
    child = parent.child("ec.peer_read", answered=0)
    cut = time.perf_counter_ns()
    assert child.finish(cut, unused=1) is True
    assert child.finish(answered=1, plane="native") is False  # its own thread, later
    parent.finish()
    (doc,) = trace.traces()[-1]["children"]
    assert doc["attrs"] == {"answered": 0, "unused": 1} and doc["end_ns"] == cut


def test_a_finished_span_takes_no_more_stages_returns_or_children(recorder):
    parent = trace.start("ec.degraded_read")
    child = parent.child("ec.peer_read")
    with trace.activate(child), child.stage("request_rtt"):
        child.finish()
        trace.turn("payload_land")  # ends `request_rtt` after the span's end
        trace.book_return(time.perf_counter_ns() - 1000)
    child.add_stage("payload_land", 0.5)
    parent.finish()
    late = parent.child("ec.peer_read")  # a fetch that got going after the close
    late.finish()
    doc = trace.traces()[-1]
    (kept,) = doc["children"]
    assert kept["stages"] == {} and kept["intervals"] == [] and "interp_returns" not in kept["attrs"]
    assert late.span_id != kept["span_id"]


def test_only_the_two_device_stages_feed_the_ewma_that_placement_reads(recorder):
    sp = trace.start("ec.encode")
    for stage, secs in (("disk_read", 9.0), ("h2d_dispatch", 0.25), ("device_drain", 0.5),
                        ("h2d_dispatch.put", 0.1), ("write_sink", 7.0)):
        sp.add_stage(stage, secs)
    sp.finish()
    ewmas = trace.stage_ewmas()
    assert ewmas == {"ec.encode/h2d_dispatch": 0.25, "ec.encode/device_drain": 0.5}
    # every stage still has its histogram and its place on the span
    assert set(trace.traces()[-1]["stages"]) >= {"disk_read", "write_sink", "h2d_dispatch"}
    view = node_view_for("n", "r", "dc", 8, 0, [], ec_telemetry={"stage_ewma_s": ewmas})
    assert view.ec_stage_ewma_s == 0.75
    # and the lock is not taken for any other stage
    class Tripwire:
        def __enter__(self):
            raise AssertionError("_ewma_lock taken for a stage nobody reads")

        def __exit__(self, *exc):
            return False

    real, trace._ewma_lock = trace._ewma_lock, Tripwire()
    try:
        sp2 = trace.start("ec.rebuild")
        sp2.add_stage("disk_read", 1.0)
        with sp2.stage("crc_verify"):
            pass
        with pytest.raises(AssertionError):
            sp2.add_stage("device_drain", 1.0)
    finally:
        trace._ewma_lock = real
