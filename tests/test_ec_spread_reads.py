"""Reads of an EC volume whose shards lie where upstream's `ec.encode` +
`ec.balance` leave them (ISSUE 35): spread over seven volume servers,
server i holding shards i and i+7, server 1 dead. A GET enters at any
live server, which reads the intervals of the needle from its own two
shards, from its peers over their native shard planes (ISSUE 37; over
`VolumeEcShardRead` where a plane does not answer), and those on a lost
shard by a reconstruction whose sibling rows it gathers from its peers.

The reference of a GET is the body written under its file id (the
seeded volume of `ecbench/data.py`); the reference of a reconstructed
interval is `ecbench/reference_decode.py`'s RS decode from the shards
that are left, which imports nothing of the program; the cluster is the
cell's own (`ecbench/spread_cluster.py`). 8 MiB, CPU.
"""

from __future__ import annotations

import http.client
import os
import time

import grpc
import numpy as np
import pytest

from ecbench import data as D
from ecbench import harness
from ecbench import reference_decode
from ecbench.spread_cluster import SpreadCluster
from seaweedfs_tpu import faults
from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as CTX
from seaweedfs_tpu.pb import cluster_pb2 as pb
from seaweedfs_tpu.utils import metrics, trace

PLAN = {
    "large_body_bytes": 1 << 20, "small_per_gib": 300,
    "small_min_bytes": 1024, "small_max_bytes": 65536, "layout_seed": 24,
}
LAYOUT = {"data_shards": 10, "parity_shards": 4,
          "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}
SEED = 2**31 + 35
PLACED = [[i, i + 7] for i in range(7)]
DOWN = 1
LOST = tuple(PLACED[DOWN])
LIVE = [i for i in range(7) if i != DOWN]


class Spread:
    """The cluster after set-up, and what the tests ask of it."""

    def __init__(self, cl, vol, shards, drop_s):
        self.cl, self.vol, self.shards, self.drop_s = cl, vol, shards, drop_s
        gets = harness.load_module("drivers", "http_gets")
        self.on_lost = sorted({
            i for sid in LOST for i in gets.needles_on_shard(vol, sid, LAYOUT)
        })
        self.healthy = [i for i in range(len(vol.sizes)) if i not in self.on_lost]

    def ev(self, s):
        return self.cl.servers[s].store.find_ec_volume(self.vol.vid)

    def healthy_on(self, sid):
        """Needles off the lost shards that have bytes on shard `sid`."""
        gets = harness.load_module("drivers", "http_gets")
        on = gets.needles_on_shard(self.vol, sid, LAYOUT)
        return [i for i in self.healthy if i in on]

    def get(self, s, i, timeout=30.0):
        conn = http.client.HTTPConnection(*self.cl.host(s), timeout=timeout)
        try:
            conn.request("GET", f"/{self.vol.fid(i)}")
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def drop_caches(self):
        for s in LIVE:
            cache = self.ev(s).interval_cache
            if cache is not None:
                cache.drop_prefix("")

    def roots(self, fid, want=1):
        """The `http.volume` roots of GETs of `fid` (an HTTP root lands
        after its response has left)."""
        deadline = time.time() + 10
        while True:
            found = [d for d in trace.traces()
                     if d["op"] == "http.volume" and d["name"] == f"GET /{fid}"]
            if len(found) >= want or time.time() > deadline:
                return found
            time.sleep(0.02)


@pytest.fixture(scope="module")
def spread(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("spread"))
    vol_dir = os.path.join(root, "vs0")
    os.makedirs(vol_dir)
    vol = D.fabricate_volume(vol_dir, 1, SEED, 8 << 20, PLAN)
    cl = SpreadCluster(
        root, {"ec_backend": "cpu", "ec_interval_cache_mb": 1}, False, servers=7
    )
    try:
        cl.wait_volume_listed(vol.vid)
        cl.shell(f"ec.encode -volumeId {vol.vid}")
        shards = []
        for i in range(CTX.total):
            with open(vol.base + CTX.to_ext(i), "rb") as f:
                shards.append(np.frombuffer(f.read(), dtype=np.uint8))
        for dst in range(1, 7):
            cl.move_shards(vol.vid, 0, dst, PLACED[dst])
        cl.wait_placement(vol.vid, PLACED)
        t0 = time.time()
        cl.stop_server(DOWN)
        cl.wait_placement(vol.vid, PLACED)
        yield Spread(cl, vol, shards, time.time() - t0)
    finally:
        faults.clear()
        trace.configure(enabled=False)
        cl.stop()


# ------------------------------------------------------------ the placement


def test_the_shards_lie_where_the_placement_says_and_the_dead_servers_stay_on_its_disk(spread):
    cl, vol = spread.cl, spread.vol
    located = cl.located(vol.vid)
    assert set(located) == set(range(CTX.total)) - set(LOST)
    for s in LIVE:
        assert spread.ev(s).shard_ids == PLACED[s]
        for sid in PLACED[s]:
            assert located[sid] == {cl.grpc_addr(s)}
        base = os.path.join(cl.dirs[s], str(vol.vid))
        assert all(os.path.exists(base + ext) for ext in (".ecx", ".vif", ".ecsum"))
        on_disk = sorted(
            int(n[-2:]) for n in os.listdir(cl.dirs[s]) if n[-5:-2] == ".ec" and n[-2:].isdigit()
        )
        assert on_disk == PLACED[s]  # the move deleted the source's copy
    assert cl.shards_beyond_placement(vol.vid, PLACED) == 0
    # a dead host's disks: nothing was unlinked
    for sid in LOST:
        assert os.path.getsize(
            os.path.join(cl.dirs[DOWN], str(vol.vid) + CTX.to_ext(sid))
        ) == len(spread.shards[sid])


def test_the_master_stops_listing_a_stopped_servers_shards_long_before_dead_after(spread):
    # the master unregisters a node when its heartbeat stream ends, which
    # a stopping server's does at once: long before `dead_after` (30 s)
    assert spread.drop_s < 10.0
    assert spread.cl.master.topo.lookup_ec(spread.vol.vid).keys().isdisjoint(LOST)


# ------------------------------------------------ bytes, against references


@pytest.mark.parametrize("entry", LIVE, ids=lambda s: f"enters_at_{s}")
def test_every_needle_through_a_live_server_is_what_was_written(spread, disarmed, entry):
    vol, ev = spread.vol, spread.ev(entry)
    built = []
    real = ev._reconstruct_range

    def recording(shard_id, offset, size, prot=None):
        out = real(shard_id, offset, size, prot)
        built.append((shard_id, offset, out))
        return out

    spread.drop_caches()
    ev._reconstruct_range = recording
    try:
        for i in range(len(vol.sizes)):
            status, body = spread.get(entry, i)
            assert status == 200 and body == vol.body(i), (entry, i, status)
    finally:
        del ev._reconstruct_range
    # every reconstructed interval is the plain decode of the shards left
    want = reference_decode.decode(
        {s: spread.shards[s] for s in range(CTX.total) if s not in LOST},
        list(LOST), CTX.data_shards, CTX.parity_shards,
    )
    assert built and {sid for sid, _o, _b in built} <= set(LOST)
    for shard_id, offset, out in built:
        assert out == want[shard_id][offset : offset + len(out)].tobytes()
        assert out == spread.shards[shard_id][offset : offset + len(out)].tobytes()
    # the rows came from peers: this server has two of the twelve left
    for _t, src in ev._coeff_cache:
        assert len(src) == CTX.data_shards and not set(src) & set(LOST)
        assert set(PLACED[entry]) <= set(src)  # its own two, and eight of its peers'


# --------------------------------------------------- a peer that is not there


def test_a_reader_whose_map_still_names_the_dead_server_fails_over_at_once(spread, disarmed):
    """The reader's map of shard locations is 10 s old at most: here it
    names the stopped server for its two shards, as it would for a GET
    that arrives just after the death. The port refuses; the read goes
    on to reconstruction, not to `VolumeEcShardRead`'s 30 s timeout."""
    cl, vol = spread.cl, spread.vol
    entry = 3
    mc = cl.servers[entry]._master_client()
    fresh = dict(mc.lookup_ec(vol.vid, refresh=True))
    dead = cl.servers[DOWN]
    stale = dict(fresh)
    for sid in LOST:
        stale[sid] = [pb.Location(
            url=f"localhost:{dead.port}", public_url=f"localhost:{dead.port}",
            grpc_port=dead.grpc_port,
        )]
    spread.drop_caches()
    with mc._lock:
        mc._ec_cache[vol.vid] = (time.time(), stale)
    try:
        t0 = time.time()
        for i in spread.on_lost[:3]:
            status, body = spread.get(entry, i)
            assert status == 200 and body == vol.body(i)
        assert time.time() - t0 < 10.0
    finally:
        with mc._lock:
            mc._ec_cache.pop(vol.vid, None)


def test_a_stale_generation_is_fenced_at_the_holder_and_reads_as_no_answer(spread, disarmed):
    cl, vol = spread.cl, spread.vol
    holder, sid = 4, PLACED[4][0]
    gen = spread.ev(holder).encode_ts_ns
    assert gen and all(spread.ev(s).encode_ts_ns == gen for s in LIVE)
    ask = pb.EcShardReadRequest(volume_id=vol.vid, shard_id=sid, offset=4096, size=8192,
                                generation=gen + 1)
    with pytest.raises(grpc.RpcError) as refused:
        list(cl.stubs[holder].VolumeEcShardRead(ask, timeout=10))
    assert refused.value.code() == grpc.StatusCode.FAILED_PRECONDITION
    read = cl.servers[0]._remote_reader_factory(vol.vid, "")
    assert read(sid, 4096, 8192, gen + 1) is None
    assert read(sid, 4096, 8192, gen) == spread.shards[sid][4096 : 4096 + 8192].tobytes()
    # a reader of another generation gets no byte from any peer: its
    # GETs are refused, never answered from the wrong volume's shards
    ev = spread.ev(0)
    spread.drop_caches()
    ev.encode_ts_ns = gen + 1
    try:
        far = next(i for i in spread.healthy
                   if spread.get(2, i)[0] == 200 and vol.sizes[i] >= 1 << 20)
        status, body = spread.get(0, far)
        assert status != 200 and bytes(vol.body(far))[:64] not in body
    finally:
        ev.encode_ts_ns = gen


# ------------------------------------------- bytes that go bad on their way


def on_shards(sids, act):
    """A `server.ec_shard_read` mutator that bends these shards' chunks."""

    def bend(ctx: dict, data: bytes) -> bytes:
        return act(ctx, data) if ctx.get("shard") in sids else data

    return bend


@pytest.mark.parametrize("how", ["rotten", "torn"])
def test_sibling_rows_that_go_bad_in_flight_are_refused_and_other_peers_taken(
    spread, disarmed, how
):
    """Ten peers' shards can answer a reconstruction at server 0 and
    eight are needed: with two of them bad on the wire, the eight rows
    that reach Reed-Solomon are exactly the other eight."""
    vol, entry = spread.vol, 0
    bad = (2, 3)  # the first two shards that server asks a peer for
    act = faults.bit_flip(seed=35, flips=3) if how == "rotten" else faults.truncate(0.5)
    ev = spread.ev(entry)
    spread.drop_caches()
    ev._coeff_cache.clear()
    trace.configure(enabled=True)
    trace.reset()
    i = spread.on_lost[0]
    with faults.injected("server.ec_shard_read", on_shards(bad, act)):
        status, body = spread.get(entry, i)
    assert status == 200 and body == vol.body(i)
    (root,) = spread.roots(vol.fid(i))
    reads = [d for d in root["children"] if d["op"] == "ec.degraded_read"
             and "reconstruct" in d["stages"]]
    assert reads
    for d in reads:
        # (an armed fault registry takes the LOCAL native plane off: this
        # server's own two rows are filled one at a time.) The peers' rows
        # are admitted together; a rotten one was filled, checked and
        # given up; a torn one is short and never counts as a row
        a = d["attrs"]
        assert a["sibling_rows_single"] == len(PLACED[entry])
        from_peers = CTX.data_shards - len(PLACED[entry])
        # (a rotten row that comes after the eighth good one is not looked at)
        rotten_seen = a["sibling_rows_batched"] - from_peers
        assert 0 <= rotten_seen <= (len(bad) if how == "rotten" else 0)
        assert a["sibling_rows_remote"] == from_peers
    assert ev._coeff_cache
    for _t, src in ev._coeff_cache:
        assert set(src).isdisjoint(bad) and set(src).isdisjoint(LOST)


def test_with_every_peer_rotten_the_get_is_refused_not_served(spread, disarmed):
    vol, entry = spread.vol, 5
    spread.drop_caches()
    rot = faults.bit_flip(seed=36, flips=2)
    i = spread.on_lost[-1]
    with faults.injected("server.ec_shard_read", rot):
        status, body = spread.get(entry, i)
    assert status != 200 and body != vol.body(i)
    status, body = spread.get(entry, i)  # and nothing rotten was cached
    assert status == 200 and body == vol.body(i)


def test_a_healthy_interval_that_rots_in_flight_fails_the_needles_crc_and_is_rebuilt(
    spread, disarmed
):
    vol, entry = spread.vol, 6
    ev = spread.ev(entry)
    i = next(i for i in spread.healthy if vol.sizes[i] >= 1 << 20)
    quiet(spread)  # no earlier read's leftover fetch takes the one rotten chunk
    rec0 = ev.bytes_reconstructed
    rot = faults.bit_flip(seed=37, flips=1)
    # the first chunk any peer streams for this GET is an interval's
    with faults.injected("server.ec_shard_read", rot, count=1):
        status, body = spread.get(entry, i)
    assert status == 200 and body == vol.body(i)
    assert ev.bytes_reconstructed > rec0  # self-heal on read: verified reconstruction


def test_a_record_whose_head_rots_in_flight_is_rebuilt_as_one_whose_body_does(spread, disarmed):
    """The body's CRC does not cover a record's head. A head that comes
    from a peer as another needle's (or as no needle's at all) is no
    answer to give: the sealed index says which record lies there."""
    vol, entry = spread.vol, 5
    ev = spread.ev(entry)
    small = LAYOUT["small_block_bytes"]

    def head_is_on_a_peer(i):
        shard = (vol.record_extent(i)[0] // small) % CTX.data_shards
        return shard not in PLACED[entry] and shard not in LOST

    i = next(i for i in spread.healthy if head_is_on_a_peer(i) and vol.sizes[i] >= 1 << 20)

    def another_needles_id(ctx: dict, data: bytes) -> bytes:
        return data[:5] + bytes([data[5] ^ 0x40]) + data[6:]  # cookie 4 bytes, then the id

    quiet(spread)
    rec0 = ev.bytes_reconstructed
    with faults.injected("server.ec_shard_read", another_needles_id, count=1):
        status, body = spread.get(entry, i)
    assert status == 200 and body == vol.body(i)
    assert ev.bytes_reconstructed > rec0


def test_servers_that_answer_from_the_wrong_shard_get_gets_refused_never_dropped_or_wrong(
    spread, disarmed
):
    """Servers 2 and 3 read each of their shards from the other one's
    file: eight good shards are left, no matrix can be filled. A GET is
    then answered right (from what is mounted where it entered) or with
    an error RESPONSE that says why; no connection is dropped on a
    record that does not parse."""
    vol = spread.vol
    spread.drop_caches()
    swapped = [(spread.ev(s), *PLACED[s]) for s in (2, 3)]
    for ev, a, b in swapped:
        ev.shard_fds[a], ev.shard_fds[b] = ev.shard_fds[b], ev.shard_fds[a]
    try:
        right = refused = 0
        for s in LIVE:
            for i in range(len(vol.sizes)):
                status, body = spread.get(s, i)  # raises where the server hangs up
                if status == 200:
                    assert body == vol.body(i)
                    right += 1
                else:
                    assert status == 404 and b'"error"' in body
                    refused += 1
        assert right and refused
    finally:
        for ev, a, b in swapped:
            ev.shard_fds[a], ev.shard_fds[b] = ev.shard_fds[b], ev.shard_fds[a]
        spread.drop_caches()
    for s in LIVE:
        status, body = spread.get(s, spread.on_lost[0])
        assert status == 200 and body == vol.body(spread.on_lost[0])


# ------------------------------------------------------ what is recorded


def served_under(root):
    """The holders' `rpc.ec_shard_read` roots under a GET's trace id (a
    stream's span lands when the stream ends, read or unread)."""
    deadline = time.time() + 10
    n = -1
    while True:
        found = [d for d in trace.traces(trace_id=root["trace_id"])
                 if d["op"] == "rpc.ec_shard_read"]
        if (found and len(found) == n) or time.time() > deadline:
            return found
        n = len(found)
        time.sleep(0.1)


COUNTERS = {
    "reads": metrics.ec_peer_reads_total,
    "bytes": metrics.ec_peer_read_bytes_total,
    "seconds": metrics.ec_peer_read_seconds_total,
}


def counted(kind, plane=None):
    """The readers' three counters for `kind`, whole process, on one
    plane (`native` | `stream`) or summed over both."""
    return {
        name: sum(v for (k, p), v in c.snapshot().items()
                  if k == kind and plane in (None, p))
        for name, c in COUNTERS.items()
    }


def grown(before, kind, plane=None):
    now = counted(kind, plane)
    return {name: now[name] - before[name] for name in now}


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_a_healthy_gets_reads_from_peers_are_counted_at_the_reader_and_seen_at_the_holders(
    spread, disarmed, armed
):
    vol, entry = spread.vol, 2
    i = next(i for i in spread.healthy if vol.sizes[i] >= 1 << 20)
    trace.configure(enabled=armed)
    trace.reset()
    quiet(spread)  # an earlier reconstruction's unread fetches have ended
    before, siblings_before = counted("interval"), counted("sibling")
    status, body = spread.get(entry, i)
    assert status == 200 and body == vol.body(i)
    got = grown(before, "interval")
    # the counters are always on; nothing of a reconstruction was asked for
    assert got["reads"] >= 1 and got["seconds"] > 0
    assert 0 < got["bytes"] <= vol.record_extent(i)[1]
    assert grown(siblings_before, "sibling") == {"reads": 0, "bytes": 0, "seconds": 0}
    if not armed:
        assert trace.traces() == []
        return
    (root,) = spread.roots(vol.fid(i))
    # nothing was recovered: the root's children are its reads from peers
    assert {c["op"] for c in root["children"]} == {"ec.peer_read"}
    st, attrs = root["stages"], root["attrs"]
    assert len(root["children"]) == attrs["peer_reads"]
    assert attrs["peer_reads"] == got["reads"] == st["peer_read"]["count"]
    assert attrs["peer_read_bytes"] == got["bytes"]
    # the reader's wait lies in the part `.peer` of `volume.read`, and is
    # what the always-on counter added up
    assert st["peer_read"]["seconds"] <= st["volume.read.peer"]["seconds"] + 1e-3
    assert st["peer_read"]["seconds"] == pytest.approx(got["seconds"], rel=0.2, abs=2e-3)
    served = served_under(root)
    assert len(served) == attrs["peer_reads"]
    assert all("stream" in d["stages"] for d in served)
    here = f"localhost:{spread.cl.servers[entry].port}"
    assert {d["server"] for d in served}.isdisjoint({here})
    assert {d["attrs"]["shard"] for d in served}.isdisjoint(set(PLACED[entry]) | set(LOST))
    # both sides count the same bytes
    assert sum(d["attrs"]["size"] for d in served) == attrs["peer_read_bytes"]


def test_a_reconstructions_fetches_are_counted_started_used_and_unused(spread, disarmed):
    vol, entry = spread.vol, 4
    i = spread.on_lost[1]
    spread.drop_caches()
    trace.configure(enabled=True)
    trace.reset()
    quiet(spread)
    before = counted("sibling")
    status, body = spread.get(entry, i)
    assert status == 200 and body == vol.body(i)
    (root,) = spread.roots(vol.fid(i))
    reads = [d for d in root["children"]
             if d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"]]
    assert reads
    from_peers = CTX.data_shards - len(PLACED[entry])
    askable = CTX.total - len(PLACED[entry]) - len(LOST)  # shards a live peer holds
    for d in reads:
        a = d["attrs"]
        # this server's own two rows in one batched read, its peers' eight
        # admitted together: no row one at a time
        assert a["sibling_rows_batched"] == len(PLACED[entry]) + from_peers
        assert a["sibling_rows_remote"] == from_peers
        assert "sibling_rows_single" not in a
        # a lost shard has no holder: it is looked up and not asked for
        assert from_peers <= a["peer_fetches_started"] <= askable
        assert a["peer_fetches_unused"] == a["peer_fetches_started"] - from_peers
        # the wait for the peers' rows is `peer_read`'s; `sibling_read` is
        # the one batched read of the two rows that lie here
        assert d["stages"]["peer_read"]["seconds"] > 0
        assert d["stages"]["sibling_read"]["count"] == 1
    # a stream that runs ends whether it is read or not: every fetch that
    # started is a read at the reader's counter and a span at its holder,
    # and both sides count the same bytes
    started = sum(d["attrs"]["peer_fetches_started"] for d in reads)
    deadline = time.time() + 10
    while grown(before, "sibling")["reads"] < started and time.time() < deadline:
        time.sleep(0.05)
    got = grown(before, "sibling")
    assert got["reads"] == started and got["seconds"] > 0
    served = served_under(root)
    assert not {d["attrs"]["shard"] for d in served} & (set(LOST) | set(PLACED[entry]))
    assert len(served) == started + root["attrs"].get("peer_reads", 0)
    assert sum(d["attrs"]["size"] for d in served) == (
        got["bytes"] + root["attrs"].get("peer_read_bytes", 0)
    )


def test_a_cached_extent_asks_no_peer_for_rows(spread, disarmed):
    """The second GET of a needle on a lost shard through the same
    server finds its extent in that server's interval cache: its healthy
    intervals still come from peers, its rows do not."""
    vol, entry = spread.vol, 5
    i = spread.on_lost[2]
    spread.drop_caches()
    assert spread.get(entry, i)[0] == 200
    trace.configure(enabled=True)
    trace.reset()
    quiet(spread)  # the first GET's unread fetches have ended
    before = counted("sibling")
    status, body = spread.get(entry, i)
    assert status == 200 and body == vol.body(i)
    (root,) = spread.roots(vol.fid(i))
    hits = [d for d in root["children"] if d["op"] == "ec.degraded_read"]
    assert hits and all("reconstruct" not in d["stages"] for d in hits)
    assert all("peer_fetches_started" not in d["attrs"] for d in hits)
    assert grown(before, "sibling")["reads"] == 0


# ------------------------------------- which plane carries a peer's range


def planes_sent(spread):
    """What the live servers' shard planes have sent, either egress."""
    planes = [spread.cl.servers[s].net_plane for s in LIVE]
    return sum(p.sendfile_bytes + p.python_bytes for p in planes)


def by_plane():
    """The readers' counters, both kinds, by plane."""
    return {plane: {kind: counted(kind, plane) for kind in ("interval", "sibling")}
            for plane in ("native", "stream")}


def plane_grown(before, plane, what):
    """Growth of the readers' `what` (reads | bytes) on `plane`, both kinds."""
    now = by_plane()[plane]
    return sum(now[kind][what] - before[plane][kind][what] for kind in now)


def bytes_grown(before, plane):
    return plane_grown(before, plane, "bytes")


def reads_grown(before, plane):
    return plane_grown(before, plane, "reads")


def settled(fn, timeout=10.0):
    """A holder books its bytes after the last one is on the wire, and a
    fetch that nobody reads ends after its GET: poll, do not race."""
    deadline = time.time() + timeout
    while not fn() and time.time() < deadline:
        time.sleep(0.02)
    return fn()


def quiet(spread, still_s=0.3):
    """(the readers' counters, the planes' bytes) once neither moves any
    more: the fetches that earlier GETs left running have ended."""
    seen, since = (by_plane(), planes_sent(spread)), time.time()
    while time.time() - since < still_s:
        time.sleep(0.05)
        now = (by_plane(), planes_sent(spread))
        if now != seen:
            seen, since = now, time.time()
    return seen


@pytest.mark.parametrize("armed", [True, False], ids=["armed", "disarmed"])
def test_interval_reads_and_sibling_rows_travel_the_native_shard_plane(spread, disarmed, armed):
    """A healthy GET and a reconstructing one through server 3: every
    byte that a peer answers crosses the peer's shard plane. Holders and
    readers count the same bytes, to the byte; no stream carries one."""
    vol, entry = spread.vol, 3
    healthy = next(i for i in spread.healthy if vol.sizes[i] >= 1 << 20)
    lost = spread.on_lost[-1]
    spread.drop_caches()
    trace.configure(enabled=armed)
    trace.reset()
    before, sent0 = quiet(spread)
    for i in (healthy, lost):
        status, body = spread.get(entry, i)
        assert status == 200 and body == vol.body(i)
    assert settled(lambda: planes_sent(spread) - sent0 == bytes_grown(before, "native") > 0)
    assert bytes_grown(before, "stream") == 0 and reads_grown(before, "stream") == 0
    now = by_plane()["native"]
    assert all(now[kind]["reads"] > before["native"][kind]["reads"]
               for kind in ("interval", "sibling"))
    if not armed:
        assert trace.traces() == []
        return
    for i in (healthy, lost):
        (root,) = spread.roots(vol.fid(i))
        attrs = root["attrs"]  # a needle that lies on lost shards alone reads no interval
        assert attrs.get("peer_reads_native", 0) == attrs.get("peer_reads", 0) >= (i == healthy)
        served = served_under(root)
        assert served and all(d["attrs"]["plane"] == "native" for d in served)
        assert all("stream" in d["stages"] for d in served)  # the stage keeps its name
        for d in root["children"]:
            if d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"]:
                a = d["attrs"]
                assert a["peer_reads_native"] == a["sibling_rows_remote"] == 8
                # the peers' rows were checked as they landed: `crc_verify` is
                # this server's own two rows, then the output row
                assert d["stages"]["crc_verify"]["count"] == 2


def test_a_peers_rows_land_in_the_matrix_the_decode_takes(spread, disarmed):
    """What `_sibling_matrix` hands to Reed-Solomon IS the shards' bytes,
    row by row, whichever fetches came first; rows that landed where
    they are used stay there (no second matrix when no spare was
    needed)."""
    vol, entry = spread.vol, 6
    ev = spread.ev(entry)
    seen = []
    real = ev._sibling_matrix

    def recording(shard_id, offset, size, prot, sp):
        matrix, ids = real(shard_id, offset, size, prot, sp)
        seen.append((offset, matrix, ids))
        return matrix, ids

    spread.drop_caches()
    ev._sibling_matrix = recording
    try:
        for i in spread.on_lost[:6]:
            status, body = spread.get(entry, i)
            assert status == 200 and body == vol.body(i)
    finally:
        del ev._sibling_matrix
    assert seen
    for offset, matrix, ids in seen:
        assert matrix.flags.c_contiguous and matrix.shape[0] == CTX.data_shards
        assert set(PLACED[entry]) <= set(ids) and not set(ids) & set(LOST)
        for row, sid in zip(matrix, ids):
            assert np.array_equal(row, spread.shards[sid][offset : offset + matrix.shape[1]])


@pytest.mark.parametrize("how", ["stopped", "refusing"])
def test_with_a_holders_plane_gone_its_ranges_come_over_the_stream_and_every_get_is_right(
    spread, disarmed, how
):
    """Server 4's shard plane is stopped (its port refuses: the client
    remembers that for 30 s) or refuses every request. Its two shards
    still reach their readers, over `VolumeEcShardRead`; the readers'
    counters say `stream` for them and `native` for the other peers'."""
    from seaweedfs_tpu.ec import net_plane

    cl, vol, holder = spread.cl, spread.vol, 4
    vs = cl.servers[holder]
    plane = vs.net_plane
    addr = net_plane.net_addr(cl.grpc_addr(holder))
    resolve = plane.resolve

    def refuse(vid, sid, gen):
        raise net_plane.NetPlaneError("not today")

    spread.drop_caches()
    if how == "stopped":
        plane.stop()
    else:
        plane.resolve = refuse
    trace.configure(enabled=True)
    trace.reset()
    before, sent0 = quiet(spread)
    try:
        on_holder = [i for sid in PLACED[holder]
                     for i in spread.healthy_on(sid)][:4]
        assert on_holder
        for entry in (0, 5):
            for i in on_holder + spread.on_lost[4:6]:
                status, body = spread.get(entry, i)
                assert status == 200 and body == vol.body(i), (entry, i, status)
        assert reads_grown(before, "stream") >= len(on_holder)
        assert bytes_grown(before, "stream") > 0 and bytes_grown(before, "native") > 0
        # the stopped or refusing plane sent nothing; the others' bytes
        # are the readers' native bytes, still to the byte
        assert settled(lambda: planes_sent(spread) - sent0 == bytes_grown(before, "native"))
        if how == "stopped":
            for entry in (0, 5):
                assert addr in cl.servers[entry]._net_plane_client()._no_plane
        # at the holder the same reads are `VolumeEcShardRead` roots
        here = f"localhost:{vs.port}"
        streamed = [d for d in trace.traces() if d["op"] == "rpc.ec_shard_read"
                    and d["server"] == here and d["attrs"].get("plane") != "native"]
        assert len(streamed) >= len(on_holder)
        assert {d["attrs"]["shard"] for d in streamed} <= set(PLACED[holder])
    finally:
        plane.resolve = resolve
        if how == "stopped":
            vs.net_plane = net_plane.ShardNetPlane(
                vs.ip, plane.port, vs._net_plane_resolve,
                server_label=plane.server_label,
            )
            vs.net_plane.start()
            for s in LIVE:  # forget the refusal and the dead sockets
                client = cl.servers[s]._net_plane_client()
                client.close()
                client.reset()
    # and with the plane back, the next read of that holder's shard takes it
    before = quiet(spread)[0]
    assert spread.get(0, on_holder[0])[0] == 200
    assert reads_grown(before, "native") >= 1 and reads_grown(before, "stream") == 0


def test_a_stale_generation_is_refused_on_the_plane_as_on_the_stream(spread, disarmed):
    from seaweedfs_tpu.ec import net_plane

    cl, vol = spread.cl, spread.vol
    holder, sid = 5, PLACED[5][1]
    gen = spread.ev(holder).encode_ts_ns
    client = cl.servers[0]._net_plane_client()
    addr = net_plane.net_addr(cl.grpc_addr(holder))
    dst = np.zeros(8192, np.uint8)
    with pytest.raises(net_plane.NetPlaneError, match="stale generation"):
        client.read_into(addr, vol.vid, sid, gen + 1, 4096, 8192, dst)
    assert not dst.any()  # refused before a byte was sent
    read = cl.servers[0]._remote_reader_factory(vol.vid, "")
    before = quiet(spread)[0]
    assert read.read_into(sid, 4096, 8192, gen + 1, dst) is None  # plane, then stream: both fence
    assert read.read_into(sid, 4096, 8192, gen, dst) == ("native", None)
    assert dst.tobytes() == spread.shards[sid][4096 : 4096 + 8192].tobytes()
    # the reader's own method counts nothing: `EcVolume._read_from_peer` does
    assert by_plane() == before
    crcs = read.read_into(sid, 0, 65536 + 100, gen, np.zeros(65536 + 100, np.uint8), 65536)
    assert crcs[0] == "native" and len(crcs[1]) == 2


def test_a_row_that_a_peer_sends_rotten_over_the_plane_never_enters_the_matrix(spread, disarmed):
    """Server 2 reads each of its two shards from the other one's file
    and `sendfile`s that: rot that no fault registry made, on the native
    egress. The CRCs rolled while those rows land are not the sidecar's:
    the rows are dropped as they arrive and the spares take their place;
    the eight rows of the decode are the other peers' and the right ones."""
    vol, entry, liar = spread.vol, 0, 2
    ev, lying = spread.ev(entry), spread.ev(liar)
    a, b = PLACED[liar]
    seen = []
    real = ev._sibling_matrix

    def recording(shard_id, offset, size, prot, sp):
        matrix, ids = real(shard_id, offset, size, prot, sp)
        seen.append((offset, matrix, ids))
        return matrix, ids

    spread.drop_caches()
    ev._coeff_cache.clear()
    trace.configure(enabled=True)
    trace.reset()
    i = spread.on_lost[0]
    lying.shard_fds[a], lying.shard_fds[b] = lying.shard_fds[b], lying.shard_fds[a]
    ev._sibling_matrix = recording
    try:
        status, body = spread.get(entry, i)
    finally:
        del ev._sibling_matrix
        lying.shard_fds[a], lying.shard_fds[b] = lying.shard_fds[b], lying.shard_fds[a]
        spread.drop_caches()
    assert status == 200 and body == vol.body(i)
    assert seen
    for offset, matrix, ids in seen:
        assert not set(ids) & {a, b} and not set(ids) & set(LOST)
        for row, sid in zip(matrix, ids):
            assert np.array_equal(row, spread.shards[sid][offset : offset + matrix.shape[1]])
    (root,) = spread.roots(vol.fid(i))
    reads = [d for d in root["children"] if d["op"] == "ec.degraded_read"
             and "reconstruct" in d["stages"]]
    assert reads
    for d in reads:
        attrs = d["attrs"]
        # ten fetches over the plane: eight rows kept, and each rotten one
        # either landed, was looked at and dropped, or came after the eighth
        assert attrs["peer_fetches_started"] == 10 and attrs["sibling_rows_remote"] == 8
        looked_at = 10 - attrs["peer_fetches_unused"]
        assert 8 <= looked_at == attrs["peer_reads_native"]
        assert attrs["sibling_rows_batched"] == len(PLACED[entry]) + looked_at
        assert "sibling_rows_single" not in attrs
        assert d["stages"]["crc_verify"]["count"] == 2  # no stage of their own for peers' rows
    served = served_under(root)
    assert all(d["attrs"]["plane"] == "native" for d in served)


def test_the_threads_that_carry_peers_bytes_have_names_and_classes_and_live_with_the_server(
    spread, disarmed
):
    import threading

    from seaweedfs_tpu.ec import ec_volume, net_plane
    from seaweedfs_tpu.utils import interp_probe

    vol, entry = spread.vol, 2
    spread.drop_caches()
    assert spread.get(entry, spread.on_lost[-1])[0] == 200

    def named(prefix):
        return {t for t in threading.enumerate() if t.name.startswith(prefix)}

    fetchers = named(ec_volume.PEER_FETCH_THREAD_PREFIX)
    conns = named(net_plane.CONN_THREAD_PREFIX)
    assert fetchers and conns
    assert {interp_probe.thread_class(t) for t in fetchers} == {"peer_fetch"}
    assert {interp_probe.thread_class(t) for t in conns} == {"shard_plane"}
    assert {t.name.rsplit("-", 1)[1] for t in conns} <= {
        str(spread.cl.servers[s].net_plane.port) for s in LIVE
    }
    # one pool per store, made once: the threads of one reconstruction
    # are there for the next
    pool = spread.cl.servers[entry].store.ec_fetch_pool
    assert spread.ev(entry)._fetch_pool is pool
    assert pool._max_workers == ec_volume.PEER_FETCH_THREADS == 32
    mine = {t for t in fetchers if t in pool._threads}
    assert mine
    quiet(spread)
    spread.drop_caches()
    assert spread.get(entry, spread.on_lost[-2])[0] == 200
    now = {t for t in named(ec_volume.PEER_FETCH_THREAD_PREFIX) if t in pool._threads}
    assert mine <= now and len(now) <= ec_volume.PEER_FETCH_THREADS
