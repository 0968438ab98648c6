"""Cluster-level EC self-healing over REAL gRPC: an in-process master +
two volume servers on loopback (the reference's in-process harness
technique, same protocols as production), with the fault registry armed
across the actual RPC boundary — mid-stream peer death, torn/corrupt
shard-read responses, latency spikes, and crash-during-distribute.

Every scenario must end bit-exact or refuse cleanly; wedging, partial
publishes, and duplicate shard copies are failures. The fixed-seed
subset runs in tier-1 (`chaos` marker); the randomized multi-fault soak
is `slow`.
"""

from __future__ import annotations

import json
import os
import threading
import time

import grpc
import numpy as np
import pytest
import requests

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec.context import ECError  # noqa: F401 (doc anchor)
from seaweedfs_tpu.ec.peer_rebuild import staging_dir
from seaweedfs_tpu.pb import cluster_pb2 as pb
from seaweedfs_tpu.pb import rpc
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer

from conftest import allocate_port as free_port
from conftest import wait_for

pytestmark = pytest.mark.chaos

TOTAL = 14  # default 10+4 ratio
KEEP_LOCAL = [0, 1, 2, 3]  # subset holder keeps 4 < k=10 shards
MOVED = list(range(4, TOTAL))


class Cluster:
    def __init__(self, tmp_path, n=2):
        self.mport = free_port()
        self.master = MasterServer(ip="localhost", port=self.mport)
        self.master.start()
        self.vols = [
            VolumeServer(
                directories=[str(tmp_path / f"v{i}")],
                master=f"localhost:{self.mport}",
                ip="localhost",
                port=free_port(),
                ec_backend="cpu",
            )
            for i in range(n)
        ]
        for vs in self.vols:
            vs.start()
        wait_for(
            lambda: len(self.master.topo.nodes) >= n,
            msg="volume servers did not register",
        )
        self._channels = []

    def stub(self, vs):
        ch = grpc.insecure_channel(f"localhost:{vs.grpc_port}")
        self._channels.append(ch)
        return rpc.volume_stub(ch)

    def locs(self, vid):
        return {
            sid: [l.url for l in locs]
            for sid, locs in self.master.topo.lookup_ec(vid).items()
        }

    def stop(self):
        for ch in self._channels:
            ch.close()
        for vs in self.vols:
            vs.stop()
        self.master.stop()


@pytest.fixture
def cluster(tmp_path):
    c = Cluster(tmp_path)
    yield c
    c.stop()


@pytest.fixture
def cluster3(tmp_path):
    c = Cluster(tmp_path, n=3)
    yield c
    c.stop()


def split_ec_volume(c: Cluster):
    """Upload + EC-encode one volume, then split the shard set so the
    uploading server becomes a SUBSET holder (4 of 14 shards — below
    k=10, the configuration local rebuild refuses on). Returns
    (vid, fid, payload, holder, other, ground: sid -> bytes)."""
    a = requests.get(f"http://localhost:{c.mport}/dir/assign").json()
    fid = a["fid"]
    vid = int(fid.split(",")[0])
    payload = np.random.default_rng(0xC10D).integers(
        0, 256, 100_000, dtype=np.uint8
    ).tobytes()
    r = requests.post(
        f"http://{a['url']}/{fid}", files={"file": ("x.bin", payload)}
    )
    assert r.status_code == 201, r.text
    holder = next(v for v in c.vols if a["url"] == f"localhost:{v.port}")
    other = next(v for v in c.vols if v is not holder)
    st_h, st_o = c.stub(holder), c.stub(other)
    st_h.VolumeEcShardsGenerate(
        pb.EcShardsGenerateRequest(volume_id=vid, backend="cpu"), timeout=120
    )
    st_h.VolumeEcShardsMount(
        pb.EcShardsMountRequest(volume_id=vid), timeout=30
    )
    st_h.VolumeDelete(pb.VolumeCommandRequest(volume_id=vid), timeout=30)
    base = holder.service._ec_base(vid, "")
    ground = {
        i: open(base + f".ec{i:02d}", "rb").read() for i in range(TOTAL)
    }
    st_o.VolumeEcShardsCopy(
        pb.EcShardsCopyRequest(
            volume_id=vid,
            shard_ids=MOVED,
            source_url=f"localhost:{holder.grpc_port}",
            copy_ecx=True, copy_ecj=True, copy_vif=True, copy_ecsum=True,
        ),
        timeout=120,
    )
    st_o.VolumeEcShardsMount(
        pb.EcShardsMountRequest(volume_id=vid), timeout=30
    )
    st_h.VolumeEcShardsUnmount(
        pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=MOVED), timeout=30
    )
    st_h.VolumeEcShardsDelete(
        pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=MOVED), timeout=30
    )
    wait_for(
        lambda: len(c.locs(vid)) == TOTAL
        and all(len(v) == 1 for v in c.locs(vid).values()),
        msg="shard split did not reach the master",
    )
    return vid, fid, payload, holder, other, ground


def quarantine(holder, vid, base, sid):
    """Scrub-style quarantine: rename the shard to .bad and unmount it."""
    os.replace(base + f".ec{sid:02d}", base + f".ec{sid:02d}.bad")
    holder.store.unmount_ec_shards(vid, [sid])


def rebuild_from_peers_rpc(c, holder, vid, timeout=120):
    st = c.stub(holder)
    return st.VolumeEcShardsRebuild(
        pb.EcShardsRebuildRequest(volume_id=vid, from_peers=True),
        timeout=timeout,
    )


# --------------------------------------------------- happy path (tier-1)


def test_peer_fetch_restores_subset_holder_bit_identical(cluster):
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    quarantine(holder, vid, base, 0)
    wait_for(
        lambda: not cluster.locs(vid).get(0),
        msg="quarantine did not reach the master",
    )
    # the per-server rebuild refuses: 3 local shards < k
    with pytest.raises(grpc.RpcError) as ei:
        cluster.stub(holder).VolumeEcShardsRebuild(
            pb.EcShardsRebuildRequest(volume_id=vid), timeout=60
        )
    assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION

    r = rebuild_from_peers_rpc(cluster, holder, vid)
    assert list(r.rebuilt_shard_ids) == [0]
    assert len(r.fetched_shard_ids) == 7  # k(10) - 3 good local
    assert open(base + ".ec00", "rb").read() == ground[0]
    ev = holder.store.find_ec_volume(vid)
    assert 0 in ev.shard_fds, "regenerated shard not remounted"
    wait_for(
        lambda: cluster.locs(vid).get(0) == [f"localhost:{holder.port}"],
        msg="restored shard not re-advertised",
    )
    # the payload still reads back through the EC read path
    got = requests.get(f"http://localhost:{holder.port}/{fid}").content
    assert got == payload


def test_flight_recorder_one_trace_spans_cluster_heal(cluster):
    """ISSUE-7 acceptance: with the flight recorder armed, one
    `ec.rebuild -fromPeers` run over REAL gRPC yields a single trace id
    spanning the rebuilding holder's RPC root and every peer shard-read
    stream, with the X-Request-ID continuous across servers; the holder
    dumps it from /debug/traces as valid Chrome trace_event JSON, and
    the per-stage histograms + overlap gauge populate for encode,
    rebuild, and degraded read."""
    from seaweedfs_tpu.utils import trace
    from seaweedfs_tpu.utils.metrics import REGISTRY

    trace.configure(enabled=True, ring_size=512)
    try:
        trace.reset()
        vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
        quarantine(holder, vid, holder.service._ec_base(vid, ""), 0)
        wait_for(
            lambda: not cluster.locs(vid).get(0),
            msg="quarantine did not reach the master",
        )
        # shard 0 now has NO holder anywhere: reading the blob forces
        # sidecar-verified reconstruction from remote siblings — the
        # degraded-read op class populates its stage histograms
        got = requests.get(f"http://localhost:{holder.port}/{fid}").content
        assert got == payload

        trace.reset()  # isolate the heal: it must mint ONE fresh trace
        r = rebuild_from_peers_rpc(cluster, holder, vid)
        assert list(r.rebuilt_shard_ids) == [0]

        docs = trace.traces()
        rebuild_roots = [
            d for d in docs if d["op"] == "rpc.ec_shards_rebuild"
        ]
        assert len(rebuild_roots) == 1
        root = rebuild_roots[0]
        tid = root["trace_id"]
        assert root["server"] == f"localhost:{holder.port}"
        assert root["attrs"]["from_peers"] is True
        # the whole heal hangs off the RPC root on the holder side
        child_ops = {ch["op"] for ch in root["children"]}
        assert "ec.peer_rebuild" in child_ops

        # every peer shard-read stream adopted the SAME trace id and
        # landed on the OTHER server — k(10) - 3 good local = 7 fetches
        reads = [
            d for d in docs
            if d["op"] == "rpc.ec_shard_read" and d["trace_id"] == tid
        ]
        assert len(reads) >= 7
        assert {d["server"] for d in reads} == {
            f"localhost:{other.port}"
        }
        assert all("stream" in d["stages"] for d in reads)
        # parent linkage points back into the holder's span tree
        holder_span_ids = set()
        def _collect(d):
            holder_span_ids.add(d["span_id"])
            for ch in d["children"]:
                _collect(ch)
        _collect(root)
        assert all(d["parent_span_id"] in holder_span_ids for d in reads)
        # request id minted once, continuous across both servers
        rids = {root["request_id"]} | {d["request_id"] for d in reads}
        assert len(rids) == 1 and "" not in rids

        # /debug/traces: valid Chrome trace_event JSON with both
        # servers as process rows for this one trace id
        resp = requests.get(
            f"http://localhost:{holder.port}/debug/traces",
            params={"trace_id": tid},
        )
        assert resp.status_code == 200
        evs = resp.json()["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        assert any(e["cat"] == "rpc.ec_shards_rebuild" for e in xs)
        assert any(e["cat"] == "rpc.ec_shard_read" for e in xs)
        assert len({e["pid"] for e in xs}) >= 2  # holder + peer rows
        for e in xs:
            assert e["dur"] > 0 and e["args"]["trace_id"] == tid
        spans = requests.get(
            f"http://localhost:{holder.port}/debug/traces",
            params={"trace_id": tid, "format": "spans"},
        ).json()
        assert {d["op"] for d in spans} >= {
            "rpc.ec_shards_rebuild", "rpc.ec_shard_read",
        }

        # per-stage histograms + overlap gauge for the three op classes
        text = REGISTRY.render().decode()
        for op in ("ec.encode", "ec.rebuild", "ec.degraded_read"):
            assert f'op="{op}"' in text, op
        assert 'sw_ec_overlap_efficiency{op="ec.encode"}' in text
        assert 'sw_ec_overlap_efficiency{op="ec.rebuild"}' in text
    finally:
        trace.configure(enabled=False)
        trace.reset()


def test_an_armed_heal_over_real_servers_times_its_sources_and_handoff(cluster):
    """The heal's `ec.peer_rebuild` span, under the holder's RPC root:
    the wait for the peers' rows (`source_land`), the hand-off of what
    it regenerated (`distribute`), and the source bytes by where they
    came from: the seven from the other server's shard plane, the three
    from the holder's own disk, none through the staging directory."""
    from seaweedfs_tpu.utils import trace

    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    quarantine(holder, vid, base, 0)
    wait_for(lambda: not cluster.locs(vid).get(0), msg="hb")
    trace.configure(enabled=True, ring_size=512)
    try:
        trace.reset()
        r = rebuild_from_peers_rpc(cluster, holder, vid)
        root = next(
            d for d in trace.traces() if d["op"] == "rpc.ec_shards_rebuild"
        )
    finally:
        trace.configure(enabled=False)
        trace.reset()
    assert list(r.rebuilt_shard_ids) == [0]
    assert open(base + ".ec00", "rb").read() == ground[0]
    (heal,) = [c for c in root["children"] if c["op"] == "ec.peer_rebuild"]
    assert {"verify", "source_land", "distribute"} <= set(heal["stages"])
    size = len(ground[0])
    attrs = heal["attrs"]
    assert attrs["source_bytes_plane"] == 7 * size
    assert attrs["source_bytes_stream"] == 0
    assert attrs["source_bytes_local"] == 3 * size
    assert attrs["source_bytes_staged"] == 0
    assert {c["op"] for c in heal["children"]} == {"ec.peer_fetch", "ec.rebuild"}


# ------------------------------------------- armed RPC faults (tier-1)


def test_peer_death_mid_stream_retries_and_converges(cluster):
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    quarantine(holder, vid, base, 1)
    wait_for(lambda: not cluster.locs(vid).get(1), msg="hb")
    with faults.injected(
        "server.ec_shard_read",
        faults.io_error("peer died mid-stream"),
        when=faults.every(3),
    ) as h:
        r = rebuild_from_peers_rpc(cluster, holder, vid)
    assert h.fired >= 1, "the peer-death fault never fired"
    assert list(r.rebuilt_shard_ids) == [1]
    assert open(base + ".ec01", "rb").read() == ground[1]


def test_latency_spike_on_peer_reads_converges(cluster):
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    quarantine(holder, vid, base, 2)
    wait_for(lambda: not cluster.locs(vid).get(2), msg="hb")
    with faults.injected(
        "server.ec_shard_read", faults.latency(0.05), when=faults.every(2)
    ) as h:
        r = rebuild_from_peers_rpc(cluster, holder, vid)
    assert h.fired >= 1
    assert list(r.rebuilt_shard_ids) == [2]
    assert open(base + ".ec02", "rb").read() == ground[2]


def test_corrupt_peer_stream_refuses_clean_then_heals(cluster):
    """The only sibling holder persistently serves corrupt bytes: the
    client's sidecar verification excludes it, exclusion leaves < k
    reachable sources, and the rebuild refuses CLEANLY over the RPC —
    no partial publish, no staging litter, no wedge. Disarming the
    fault and re-running converges bit-exact."""
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    quarantine(holder, vid, base, 3)
    wait_for(lambda: not cluster.locs(vid).get(3), msg="hb")
    with faults.injected(
        "server.ec_shard_read", faults.bit_flip(seed=0xBAD, flips=4)
    ):
        with pytest.raises(grpc.RpcError) as ei:
            rebuild_from_peers_rpc(cluster, holder, vid)
    assert ei.value.code() == grpc.StatusCode.FAILED_PRECONDITION
    assert "refusing" in ei.value.details()
    assert not os.path.exists(base + ".ec03"), "partial publish!"
    assert not os.path.exists(staging_dir(base)), "staging litter"
    # registry disarmed (context manager): the same call now converges
    r = rebuild_from_peers_rpc(cluster, holder, vid)
    assert list(r.rebuilt_shard_ids) == [3]
    assert open(base + ".ec03", "rb").read() == ground[3]
    got = requests.get(f"http://localhost:{holder.port}/{fid}").content
    assert got == payload


def test_crash_during_distribute_rerun_no_duplicates(cluster):
    """A cluster-lost shard is rebuilt on the BIG holder (so the
    placement planner routes the regenerated shard to the smaller
    peer), and the rebuilder CRASHES after the destination mounted the
    copy but before the local handoff file was cleaned. The re-run must
    converge to EXACTLY ONE holder — finishing the handoff by deleting
    the local duplicate, never copying to a second destination."""
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    # lose shard 13 cluster-wide (it lived on `other`, the big holder)
    st_o = cluster.stub(other)
    st_o.VolumeEcShardsUnmount(
        pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    st_o.VolumeEcShardsDelete(
        pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    wait_for(lambda: not cluster.locs(vid).get(13), msg="shard13 not lost")

    with faults.injected(
        "ec.peer_rebuild.after_distribute", faults.crash(),
        when=faults.nth_call(1),
    ) as h:
        # in-process call so the InjectedCrash (a BaseException) models
        # the process dying inside the distribute window; the big
        # holder (9 local shards) rebuilds, the planner picks the
        # 4-shard subset holder as the destination
        with pytest.raises(faults.InjectedCrash):
            other.peer_fetch_rebuild(vid)
    assert h.fired == 1, "crash window never reached (no distribution?)"
    # crash state: destination mounted the shard, rebuilder still has
    # the unmounted handoff file on disk
    obase = other.service._ec_base(vid, "")
    assert os.path.exists(obase + ".ec13"), "handoff file missing"
    wait_for(
        lambda: len(cluster.locs(vid).get(13, [])) >= 1,
        msg="no holder advertises shard 13 after crash window",
    )
    # re-run: idempotent convergence, no second copy
    out = other.peer_fetch_rebuild(vid)
    assert 13 not in out["rebuilt"], "re-run must not regenerate again"
    wait_for(
        lambda: len(cluster.locs(vid).get(13, [])) == 1,
        msg="shard 13 not at exactly one holder",
    )
    copies = 0
    for vs in cluster.vols:
        b = vs.service._ec_base(vid, "")
        if b and os.path.exists(b + ".ec13"):
            assert open(b + ".ec13", "rb").read() == ground[13]
            copies += 1
    assert copies == 1, f"{copies} on-disk copies of shard 13 (want 1)"


# ------------------------------------- fleet scrub control loop (tier-1)


def test_fleet_scrub_dispatches_peer_fetch_and_heals(cluster):
    """The whole loop: fleet scrub task -> per-holder scrub over gRPC ->
    unrebuildable holder detected (quarantined shard, < k good local) ->
    master dispatches ec_rebuild -fromPeers -> worker drives the RPC ->
    shard healed bit-exact; aggregation lands in /cluster/status and the
    fleet gauges."""
    from seaweedfs_tpu.worker.worker import Worker

    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    w = Worker(master=f"localhost:{cluster.mport}", backend="cpu")
    wt = threading.Thread(target=w.run, daemon=True)
    wt.start()
    try:
        wait_for(
            lambda: cluster.master.worker_control._workers,
            msg="worker did not register",
        )
        quarantine(holder, vid, base, 0)
        wait_for(lambda: not cluster.locs(vid).get(0), msg="hb")
        tids = cluster.master.worker_control.scan_for_ec_scrub(
            cluster.master.topo, 0.001
        )
        assert tids, "fleet scanner submitted nothing"
        # second sweep within the period: volume not due again
        assert not cluster.master.worker_control.scan_for_ec_scrub(
            cluster.master.topo, 3600.0
        )
        wait_for(
            lambda: cluster.master.worker_control.scrub_reports.get(vid),
            timeout=60,
            msg="scrub report never aggregated",
        )
        summary = cluster.master.worker_control.scrub_summary()
        assert vid in summary["unrebuildable_volumes"], summary
        hrep = summary["reports"][vid]["holders"][
            f"localhost:{holder.port}"
        ]
        assert hrep["quarantined"] == [0] and hrep["unrebuildable"]
        wait_for(
            lambda: 0 in (holder.store.find_ec_volume(vid).shard_fds),
            timeout=60,
            msg="dispatched peer-fetch rebuild never healed the shard",
        )
        assert open(base + ".ec00", "rb").read() == ground[0]
        cs = requests.get(
            f"http://localhost:{cluster.mport}/cluster/status"
        ).json()
        assert cs["EcFleetScrub"]["volumes"] >= 1
        assert vid in {
            int(k) for k in cs["EcFleetScrub"]["reports"]
        }
        _, tasks = cluster.master.worker_control.snapshot()
        kinds = {t["kind"]: t["state"] for t in tasks}
        assert kinds.get("ec_scrub") == "done"
        wait_for(
            lambda: any(
                t["kind"] == "ec_rebuild" and t["state"] == "done"
                for t in cluster.master.worker_control.snapshot()[1]
            ),
            timeout=30,
            msg="ec_rebuild task did not finish",
        )
        # next scrub period: the holder is healed — the forensic .bad
        # file still on disk must NOT mark it quarantined/unrebuildable
        # again, or the fleet loop would dispatch a no-op rebuild at it
        # every period forever
        assert os.path.exists(base + ".ec00.bad"), "forensic copy gone"
        before = sum(
            1
            for t in cluster.master.worker_control.snapshot()[1]
            if t["kind"] == "ec_rebuild"
        )
        ts0 = cluster.master.worker_control.scrub_reports[vid]["ts"]
        assert cluster.master.worker_control.scan_for_ec_scrub(
            cluster.master.topo, 0.001
        ), "second-period scan submitted nothing"
        wait_for(
            lambda: cluster.master.worker_control.scrub_reports[vid]["ts"]
            > ts0,
            timeout=60,
            msg="second scrub report never aggregated",
        )
        hrep2 = cluster.master.worker_control.scrub_reports[vid][
            "holders"
        ][f"localhost:{holder.port}"]
        assert hrep2["quarantined"] == [], hrep2
        assert not hrep2["unrebuildable"], hrep2
        after = sum(
            1
            for t in cluster.master.worker_control.snapshot()[1]
            if t["kind"] == "ec_rebuild"
        )
        assert after == before, "healed holder was dispatched at again"
    finally:
        w.stop()


def test_failed_distribute_leftover_not_mounted_by_task_driver(cluster):
    """When distributing a regenerated cluster-lost shard fails (dest
    unreachable), the handoff copy stays on the rebuilder's disk but
    must remain UNMOUNTED and unadvertised — the worker task driver
    must not blanket-mount it (that would advertise a holder whose copy
    the next run's dedupe pass then unlinks). A re-run with the dest
    healthy finishes the handoff to exactly one holder."""
    from seaweedfs_tpu.worker.worker import Worker

    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    # lose shard 13 cluster-wide; `other` (the big holder) rebuilds it,
    # so the planner routes the regenerated copy at the subset holder
    st_o = cluster.stub(other)
    st_o.VolumeEcShardsUnmount(
        pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    st_o.VolumeEcShardsDelete(
        pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    wait_for(lambda: not cluster.locs(vid).get(13), msg="shard13 not lost")

    class _CopyDown(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.UNAVAILABLE

        def details(self):
            return "injected: destination down"

    real_stub = other._peer_stub

    class _Proxy:
        def __init__(self, stub):
            self._stub = stub

        def __getattr__(self, name):
            if name == "VolumeEcShardsCopy":
                def _boom(*a, **k):
                    raise _CopyDown()
                return _boom
            return getattr(self._stub, name)

    w = Worker(master=f"localhost:{cluster.mport}", backend="cpu")
    wt = threading.Thread(target=w.run, daemon=True)
    wt.start()
    try:
        wait_for(
            lambda: cluster.master.worker_control._workers,
            msg="worker did not register",
        )
        other._peer_stub = lambda dest: _Proxy(real_stub(dest))
        try:
            cluster.master.worker_control.submit(
                "ec_rebuild",
                vid,
                "",
                params={
                    "fromPeers": "true",
                    "holder": f"localhost:{other.grpc_port}",
                },
            )
            wait_for(
                lambda: any(
                    t["kind"] == "ec_rebuild" and t["state"] == "done"
                    for t in cluster.master.worker_control.snapshot()[1]
                ),
                timeout=60,
                msg="ec_rebuild task did not finish",
            )
        finally:
            other._peer_stub = real_stub
        obase = other.service._ec_base(vid, "")
        assert os.path.exists(obase + ".ec13"), "handoff copy not kept"
        ev_o = other.store.find_ec_volume(vid)
        assert 13 not in ev_o.shard_fds, (
            "task driver mounted the failed-handoff copy"
        )
        time.sleep(1.5)  # a heartbeat round: it must NOT advertise 13
        assert not cluster.locs(vid).get(13), (
            "failed-handoff copy was advertised to the master"
        )
        # dest healthy again: re-run finishes the handoff, one holder
        cluster.master.worker_control.submit(
            "ec_rebuild",
            vid,
            "",
            params={
                "fromPeers": "true",
                "holder": f"localhost:{other.grpc_port}",
            },
        )
        wait_for(
            lambda: len(cluster.locs(vid).get(13, [])) == 1,
            timeout=60,
            msg="handoff never completed to exactly one holder",
        )
        wait_for(
            lambda: not os.path.exists(obase + ".ec13"),
            msg="local handoff copy not cleaned after successful handoff",
        )
        hbase = holder.service._ec_base(vid, "")
        assert open(hbase + ".ec13", "rb").read() == ground[13]
    finally:
        other._peer_stub = real_stub
        w.stop()


def test_distribute_replans_to_surviving_holder_in_pass(cluster3):
    """ISSUE-8 satellite: the FIRST planned destination dies mid-copy
    and the distribute step re-plans IN THE SAME RUN — the regenerated
    cluster-lost shard lands on exactly one SURVIVING alternate holder,
    no deferred handoff, and a re-run is an idempotent no-op."""
    c = cluster3
    vid, fid, payload, holder, other, ground = split_ec_volume(c)
    third = next(v for v in c.vols if v is not holder and v is not other)
    # lose shard 13 cluster-wide (it lived on `other`, the big holder)
    st_o = c.stub(other)
    st_o.VolumeEcShardsUnmount(
        pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    st_o.VolumeEcShardsDelete(
        pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    wait_for(lambda: not cluster3.locs(vid).get(13), msg="shard13 not lost")

    # `third` holds ZERO shards of this volume, so the planner picks it
    # first; every copy to it fails as if it died mid-distribute
    third_grpc = f"localhost:{third.grpc_port}"
    failed = {"n": 0}

    class _CopyDown(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.UNAVAILABLE

        def details(self):
            return "injected: destination died mid-copy"

    real_stub = other._peer_stub

    def picky_stub(dest):
        stub = real_stub(dest)
        if dest != third_grpc:
            return stub

        class _Proxy:
            def __getattr__(self, name):
                if name == "VolumeEcShardsCopy":
                    def _boom(*a, **k):
                        failed["n"] += 1
                        raise _CopyDown()
                    return _boom
                return getattr(stub, name)

        return _Proxy()

    other._peer_stub = picky_stub
    try:
        out = other.peer_fetch_rebuild(vid)
    finally:
        other._peer_stub = real_stub
    assert failed["n"] == 1, "first destination never tried"
    # the SAME run re-planned and finished the handoff elsewhere
    assert out["distributed"] == [13], out
    wait_for(
        lambda: len(c.locs(vid).get(13, [])) == 1,
        msg="shard 13 not at exactly one holder after in-pass re-plan",
    )
    assert c.locs(vid)[13] == [f"localhost:{holder.port}"], (
        "re-plan must land on the surviving subset holder"
    )
    copies = 0
    for vs in c.vols:
        b = vs.service._ec_base(vid, "")
        if b and os.path.exists(b + ".ec13"):
            assert open(b + ".ec13", "rb").read() == ground[13]
            copies += 1
    assert copies == 1, f"{copies} on-disk copies of shard 13 (want 1)"
    # idempotent re-run: nothing left to regenerate or distribute
    out2 = other.peer_fetch_rebuild(vid)
    assert 13 not in out2["rebuilt"] and not out2["distributed"]


def test_distribute_mount_failure_cleans_dest_copy(cluster3):
    """Copy SUCCEEDS but the mount fails: the re-plan must not leave a
    latent duplicate on the failed destination — the distribute step
    issues a best-effort delete before excluding it, so the shard ends
    at exactly one holder with exactly one on-disk copy cluster-wide."""
    c = cluster3
    vid, fid, payload, holder, other, ground = split_ec_volume(c)
    third = next(v for v in c.vols if v is not holder and v is not other)
    st_o = c.stub(other)
    st_o.VolumeEcShardsUnmount(
        pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    st_o.VolumeEcShardsDelete(
        pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=[13]), timeout=30
    )
    wait_for(lambda: not cluster3.locs(vid).get(13), msg="shard13 not lost")

    third_grpc = f"localhost:{third.grpc_port}"

    class _MountDown(grpc.RpcError):
        def code(self):
            return grpc.StatusCode.DEADLINE_EXCEEDED

        def details(self):
            return "injected: mount timed out"

    real_stub = other._peer_stub

    def picky_stub(dest):
        stub = real_stub(dest)
        if dest != third_grpc:
            return stub

        class _Proxy:
            def __getattr__(self, name):
                if name == "VolumeEcShardsMount":
                    def _boom(*a, **k):
                        raise _MountDown()
                    return _boom
                return getattr(stub, name)

        return _Proxy()

    other._peer_stub = picky_stub
    try:
        out = other.peer_fetch_rebuild(vid)
    finally:
        other._peer_stub = real_stub
    assert out["distributed"] == [13], out
    wait_for(
        lambda: len(c.locs(vid).get(13, [])) == 1,
        msg="shard 13 not at exactly one holder",
    )
    # the failed destination's copied files were cleaned: exactly one
    # on-disk copy anywhere (a later mount on `third` can no longer
    # resurrect a duplicate holder)
    tbase = third.service._ec_base(vid, "")
    assert tbase is None or not os.path.exists(tbase + ".ec13"), (
        "copy left on the mount-failed destination"
    )
    copies = 0
    for vs in c.vols:
        b = vs.service._ec_base(vid, "")
        if b and os.path.exists(b + ".ec13"):
            assert open(b + ".ec13", "rb").read() == ground[13]
            copies += 1
    assert copies == 1, f"{copies} on-disk copies of shard 13 (want 1)"


def test_concurrent_peer_rebuild_refuses_cleanly(cluster):
    """Only one peer-fetch rebuild per volume runs on a server at a
    time: a second concurrent call (shell racing the fleet dispatcher)
    would wipe the first call's staging mid-flight, so it refuses with
    a clean ECError instead."""
    import threading as _threading

    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    busy = holder._peer_rebuild_busy.setdefault(vid, _threading.Lock())
    busy.acquire()
    try:
        with pytest.raises(ECError, match="already"):
            holder.peer_fetch_rebuild(vid)
    finally:
        busy.release()
    # released: the same call now proceeds (nothing to rebuild is fine)
    out = holder.peer_fetch_rebuild(vid)
    assert out["rebuilt"] == []


def test_total_loss_holder_flagged_unrebuildable_and_healed(cluster):
    """A holder whose EVERY shard file is gone (sidecar survives, fds
    still advertised) checks zero shards — the fleet scrub must report
    it all-missing/unrebuildable, not healthy, and the dispatched
    peer-fetch rebuild restores all of its shards bit-exact."""
    from seaweedfs_tpu.worker.worker import Worker

    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    for sid in KEEP_LOCAL:
        os.remove(base + f".ec{sid:02d}")
    w = Worker(master=f"localhost:{cluster.mport}", backend="cpu")
    wt = threading.Thread(target=w.run, daemon=True)
    wt.start()
    try:
        wait_for(
            lambda: cluster.master.worker_control._workers,
            msg="worker did not register",
        )
        tids = cluster.master.worker_control.scan_for_ec_scrub(
            cluster.master.topo, 0.001
        )
        assert tids, "fleet scanner submitted nothing"
        wait_for(
            lambda: cluster.master.worker_control.scrub_reports.get(vid),
            timeout=60,
            msg="scrub report never aggregated",
        )
        hrep = cluster.master.worker_control.scrub_reports[vid]["holders"][
            f"localhost:{holder.port}"
        ]
        assert hrep["missing"] == KEEP_LOCAL, hrep
        assert hrep["unrebuildable"], (
            "total-loss holder reported as rebuildable/healthy"
        )
        wait_for(
            lambda: all(
                os.path.exists(base + f".ec{sid:02d}")
                for sid in KEEP_LOCAL
            ),
            timeout=60,
            msg="dispatched peer-fetch rebuild never restored the shards",
        )
        for sid in KEEP_LOCAL:
            assert open(base + f".ec{sid:02d}", "rb").read() == ground[sid]
        got = requests.get(f"http://localhost:{holder.port}/{fid}").content
        assert got == payload
    finally:
        w.stop()


def test_rotten_handoff_leftover_regenerated_but_never_mounted(cluster):
    """A leftover handoff copy that ROTTED on disk (canonical filename,
    unmounted, outside this server's legitimate set) is replaced by the
    rebuild's verify-and-exclude pass, but must never be mounted or
    advertised here — the dedupe pass hands it back to the holder that
    already serves it. Mounting it would advertise a second holder whose
    file the same call then unlinks."""
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    # quarantine shard 0 so the rebuild has legitimate work
    quarantine(holder, vid, base, 0)
    wait_for(lambda: not cluster.locs(vid).get(0), msg="hb")
    # plant a rotten leftover of shard 13 (still served by `other`)
    rot = bytearray(ground[13])
    rot[7] ^= 0xFF
    with open(base + ".ec13", "wb") as f:
        f.write(rot)
    out = holder.peer_fetch_rebuild(vid)
    assert 0 in out["rebuilt"], out
    ev = holder.store.find_ec_volume(vid)
    assert 0 in ev.shard_fds, "quarantined shard not remounted"
    assert 13 not in ev.shard_fds, (
        "non-legitimate regenerated shard was mounted"
    )
    assert not os.path.exists(base + ".ec13"), (
        "dedupe pass did not clean the leftover"
    )
    assert cluster.locs(vid).get(13) == [f"localhost:{other.port}"]
    assert open(base + ".ec00", "rb").read() == ground[0]


# ------------------------------------------------ randomized soak (slow)


@pytest.mark.slow
def test_randomized_multi_fault_soak(cluster):
    """Random fault cocktails over the peer-rebuild RPC path: every
    round must converge bit-exact or refuse cleanly — wrong bytes on
    disk after a claimed success is a silent-corruption bug."""
    vid, fid, payload, holder, other, ground = split_ec_volume(cluster)
    base = holder.service._ec_base(vid, "")
    rng = np.random.default_rng(0x50AC)
    for round_i in range(5):
        sid = int(rng.integers(0, 4))
        path = base + f".ec{sid:02d}"
        if os.path.exists(path):
            quarantine(holder, vid, base, sid)
            wait_for(lambda: not cluster.locs(vid).get(sid), msg="hb")
        handles = []
        for point in ("server.ec_shard_read", "ec.peer_fetch.read"):
            roll = rng.random()
            if roll < 0.35:
                handles.append(
                    faults.inject(
                        point,
                        faults.io_error("soak"),
                        when=faults.probability(
                            0.3, seed=int(rng.integers(1 << 30))
                        ),
                    )
                )
            elif roll < 0.6:
                handles.append(
                    faults.inject(
                        point,
                        faults.bit_flip(
                            seed=int(rng.integers(1 << 30)), flips=2
                        ),
                        when=faults.probability(
                            0.3, seed=int(rng.integers(1 << 30))
                        ),
                    )
                )
        refused = False
        try:
            rebuild_from_peers_rpc(cluster, holder, vid, timeout=180)
        except grpc.RpcError as e:
            refused = True
            assert e.code() == grpc.StatusCode.FAILED_PRECONDITION, e
        finally:
            for h in handles:
                h.remove()
        if os.path.exists(path):
            assert open(path, "rb").read() == ground[sid], (
                f"round {round_i}: SILENT CORRUPTION on shard {sid} "
                f"(refused={refused})"
            )
        else:
            assert refused, "no publish without refusal"
            # disarmed retry must converge before the next round
            rebuild_from_peers_rpc(cluster, holder, vid, timeout=180)
            assert open(path, "rb").read() == ground[sid]
        assert not os.path.exists(staging_dir(base)), "staging litter"
    # final state: everything mounted and the payload reads back
    got = requests.get(f"http://localhost:{holder.port}/{fid}").content
    assert got == payload
