"""ISSUE 11: gateway read path at production concurrency.

The serving-side contract under load: (1) the bounded worker-pool HTTP
front end degrades gracefully (keep-alive reuse, park/resume, explicit
503 + Retry-After with a parseable body at saturation — never unbounded
thread spawn or silent collapse); (2) the hot-chunk cache collapses
concurrent misses to ONE degraded reconstruction (singleflight) and
never serves a stale generation after remount/rebuild invalidation;
(3) with the fault registry ARMED (one shard dead + injected latency)
and >=32 concurrent clients, every response is byte-correct or a clean
503 — no hangs, no corrupt bodies — while gateway reads run in the
scheduler's FOREGROUND class (visible via span stage attribution).
"""

from __future__ import annotations

import json
import socket
import threading
import time
import xml.etree.ElementTree as ET

import numpy as np
import pytest
import requests

from conftest import allocate_port as free_port

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import CpuBackend, ECContext, EcVolume, ec_encode_volume
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import trace
from seaweedfs_tpu.utils.http_pool import PooledHTTPServer

CTX = ECContext(10, 4)


def _wait(cond, timeout=20.0, msg="condition"):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, f"timed out: {msg}"
        time.sleep(0.05)


# =====================================================================
# Pooled HTTP front end (utils/http_pool.py)
# =====================================================================


def _make_echo_handler():
    from http.server import BaseHTTPRequestHandler

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b"echo:" + self.path.encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    return H


def test_pooled_server_keepalive_park_resume():
    """A keep-alive connection survives idle parking: requests flow,
    the connection parks (no worker pinned), and a later request on the
    SAME connection is served."""
    srv = PooledHTTPServer(
        ("127.0.0.1", 0), _make_echo_handler(), workers=2, accept_queue=4,
        server_kind="test",
    )
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        sess = requests.Session()
        assert sess.get(f"http://127.0.0.1:{port}/a").content == b"echo:/a"
        time.sleep(1.0)  # parked well past any dispatch loop
        assert sess.get(f"http://127.0.0.1:{port}/b").content == b"echo:/b"
        # the worker counts a request after handle_one_request returns,
        # which is after the client has its reply: wait, don't race it
        _wait(
            lambda: srv.pool_status()["requests_served"] >= 2,
            msg="requests_served to reach 2",
        )
        assert srv.pool_status()["open_connections"] >= 1  # the parked conn
    finally:
        srv.shutdown()
        srv.server_close()


def test_pooled_server_bounded_and_503_shape():
    """Past workers + accept_queue live connections, a new connection
    is answered 503 + Retry-After with the configured body — explicit
    backpressure, not an unbounded thread or a hung accept."""
    srv = PooledHTTPServer(
        ("127.0.0.1", 0), _make_echo_handler(), workers=1, accept_queue=1,
        server_kind="test",
        reject_body=lambda: ("application/json", b'{"error": "full"}'),
    )
    port = srv.server_address[1]
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    helds = []
    try:
        # hold max_connections=2 idle keep-alive connections
        for _ in range(2):
            c = socket.create_connection(("127.0.0.1", port))
            helds.append(c)
        time.sleep(0.3)  # let the acceptor admit both
        r = requests.get(f"http://127.0.0.1:{port}/x", timeout=5)
        assert r.status_code == 503
        assert r.headers.get("Retry-After")
        assert r.headers.get("Content-Type") == "application/json"
        assert json.loads(r.content)["error"] == "full"
        assert srv.pool_status()["rejected_total"] >= 1
        # draining a held connection frees budget for new clients
        helds.pop().close()
        _wait(
            lambda: requests.get(
                f"http://127.0.0.1:{port}/y", timeout=5
            ).status_code == 200,
            msg="admission after a connection freed",
        )
    finally:
        for c in helds:
            c.close()
        srv.shutdown()
        srv.server_close()


def test_pooled_server_concurrent_correctness():
    """More concurrent clients than workers: every response still maps
    to ITS request (no cross-connection body mixups under dispatch)."""
    srv = PooledHTTPServer(
        ("127.0.0.1", 0), _make_echo_handler(), workers=4, accept_queue=64,
        server_kind="test",
    )
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    errors = []

    def client(i):
        try:
            sess = requests.Session()
            for j in range(5):
                r = sess.get(f"http://127.0.0.1:{port}/c{i}-{j}", timeout=15)
                assert r.status_code == 200
                assert r.content == b"echo:/c%d-%d" % (i, j)
        except Exception as e:
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(24)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    alive = [t for t in threads if t.is_alive()]
    try:
        assert not alive, f"{len(alive)} clients hung"
        assert not errors, errors[:5]
        assert time.time() - t0 < 60
    finally:
        srv.shutdown()
        srv.server_close()


def test_s3_saturation_returns_wellformed_error_document():
    """The S3 plane's 503 body parses as an S3 error document
    (Code=SlowDown) so SDK clients back off instead of choking."""
    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.s3 import S3Server

    filer = Filer(MemoryStore(), master="localhost:1")
    srv = S3Server(
        filer, ip="127.0.0.1", port=free_port(),
        lifecycle_interval=0, http_workers=1, http_queue=0,
    )
    srv.start()
    helds = []
    try:
        helds.append(socket.create_connection(("127.0.0.1", srv.port)))
        time.sleep(0.3)
        r = requests.get(f"http://127.0.0.1:{srv.port}/", timeout=5)
        assert r.status_code == 503
        assert r.headers.get("Retry-After")
        doc = ET.fromstring(r.content)
        assert doc.tag == "Error"
        assert doc.findtext("Code") == "SlowDown"
        assert doc.findtext("Message")
    finally:
        for c in helds:
            c.close()
        srv.stop()
        filer.close()


# =====================================================================
# Hot-chunk cache semantics on the EC degraded-read path
# =====================================================================


def _make_degraded_volume(tmp_path, vid=1, needles=24, seed=3):
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), vid)
    payloads = {}
    for i in range(1, needles + 1):
        size = int(rng.integers(2_000, 30_000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x2000 + i, needle_id=i, data=data))
        payloads[i] = data
    v.close()
    base = Volume.base_file_name(str(tmp_path), "", vid)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    vol = EcVolume(str(tmp_path), vid, backend_name="cpu")
    vol.unmount_shards([0])  # degrade: stripe-0 reads must reconstruct
    return vol, payloads


def _needle_on_shard0(vol, needles=24) -> int:
    """A needle whose whole record lives on shard 0 (single-interval
    reconstruction — deterministic singleflight key)."""
    from seaweedfs_tpu.ec.locate import locate_data
    from seaweedfs_tpu.storage.types import actual_offset
    from seaweedfs_tpu.ec.decoder import record_actual_size

    for nid in range(1, needles + 1):
        nv = vol._ecx.get(nid)
        ivs = list(
            locate_data(
                actual_offset(nv.offset),
                record_actual_size(nv.size, vol.version),
                vol._locate_shard_size,
                CTX.data_shards,
            )
        )
        if len(ivs) == 1:
            sid, _ = ivs[0].to_shard_and_offset(CTX.data_shards)
            if sid == 0:
                return nid
    pytest.skip("no single-interval needle landed on shard 0")


def test_concurrent_degraded_reads_collapse_to_one_reconstruction(tmp_path):
    """THE tentpole assert: K concurrent misses on one degraded chunk
    -> exactly ONE reconstruction, all K responses byte-identical."""
    vol, payloads = _make_degraded_volume(tmp_path)
    nid = _needle_on_shard0(vol)

    recon_calls = []
    orig = vol.backend.apply  # a reconstruction is ONE apply of its matrix
    gate = threading.Event()

    def counting_apply(coeffs, matrix):
        recon_calls.append(matrix.shape)
        gate.wait(5)  # hold the leader so every reader joins the flight
        return orig(coeffs, matrix)

    vol.backend.apply = counting_apply
    results, errors = [], []
    lock = threading.Lock()

    def reader():
        try:
            n = vol.read_needle(nid)
            with lock:
                results.append(n.data)
        except Exception as e:
            with lock:
                errors.append(repr(e))

    threads = [threading.Thread(target=reader) for _ in range(12)]
    for t in threads:
        t.start()
    _wait(lambda: len(recon_calls) >= 1, msg="leader reconstruction")
    time.sleep(0.2)  # let every follower pile onto the flight
    gate.set()
    for t in threads:
        t.join(timeout=30)
    assert not errors, errors[:3]
    assert len(results) == 12
    assert all(r == payloads[nid] for r in results), "byte-identity"
    assert len(recon_calls) == 1, (
        f"{len(recon_calls)} reconstructions for 12 concurrent reads "
        "(singleflight must collapse them to 1)"
    )
    assert vol.interval_cache.singleflight_waits >= 11
    # the flight's verified output is now cached: a fresh read is free
    vol.read_needle(nid)
    assert len(recon_calls) == 1
    vol.close()


def test_invalidation_never_serves_stale_generation(tmp_path):
    """Remount/rebuild bumps the shard generation: cached extents (and
    any in-flight load parked under the old key) become invisible — the
    next read reconstructs fresh bytes."""
    vol, payloads = _make_degraded_volume(tmp_path, seed=5)
    nid = _needle_on_shard0(vol)

    recon_calls = []
    orig = vol.backend.apply

    def counting_apply(coeffs, matrix):
        recon_calls.append(matrix.shape)
        return orig(coeffs, matrix)

    vol.backend.apply = counting_apply
    assert vol.read_needle(nid).data == payloads[nid]
    assert len(recon_calls) == 1
    assert vol.read_needle(nid).data == payloads[nid]
    assert len(recon_calls) == 1, "second read must be a cache hit"
    # invalidate shard 0's cached extents (what rebuild/remount do)
    vol.reopen_shards([0])
    vol.unmount_shards([0])  # re-degrade (reopen remounted the shard)
    assert vol.read_needle(nid).data == payloads[nid]
    assert len(recon_calls) == 2, (
        "a generation bump must force a fresh reconstruction — the old "
        "cached extent may be stale"
    )
    vol.close()


# =====================================================================
# Chaos under gateway load (the carried PR 1 variant)
# =====================================================================


@pytest.fixture(scope="module")
def gateway_cluster(tmp_path_factory):
    """Real in-process cluster (master + pooled volume server + pooled
    S3 gateway) over ONE object on a DEGRADED EC volume."""
    import grpc

    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.pb import cluster_pb2 as cpb
    from seaweedfs_tpu.pb import rpc as _rpc
    from seaweedfs_tpu.s3 import S3Server
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellEnv, run_command
    from seaweedfs_tpu.storage.file_id import FileId

    tmp = tmp_path_factory.mktemp("gwload")
    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[str(tmp / "v")],
        master=f"localhost:{mport}",
        ip="localhost",
        port=free_port(),
        ec_backend="cpu",
    )
    vs.start()
    _wait(lambda: master.topo.nodes, msg="volume registration")
    filer = Filer(
        MemoryStore(), master=f"localhost:{mport}", chunk_size=32 * 1024
    )
    s3 = S3Server(filer, ip="localhost", port=free_port())
    s3.start()
    base = f"http://localhost:{s3.port}"
    rng = np.random.default_rng(0xC0FFEE)
    data = rng.integers(0, 256, 128 << 10, dtype=np.uint8).tobytes()
    assert requests.put(f"{base}/load").status_code == 200
    assert requests.put(f"{base}/load/obj", data=data).status_code == 200
    entry = filer.find_entry("/buckets/load/obj")
    vid = FileId.parse(entry.chunks[0].fid).volume_id
    env = ShellEnv(f"localhost:{mport}")
    try:
        out = run_command(env, f"ec.encode -volumeId {vid} -backend cpu")
        assert "generation" in out, out
    finally:
        env.close()
    _wait(
        lambda: any(vid in n.ec_shards for n in master.topo.nodes.values()),
        msg="ec shards via heartbeat",
    )
    with grpc.insecure_channel(f"localhost:{vs.grpc_port}") as ch:
        _rpc.volume_stub(ch).VolumeEcShardsUnmount(
            cpb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[0])
        )
    yield {
        "master": master,
        "vs": vs,
        "filer": filer,
        "s3": s3,
        "base": base,
        "data": data,
        "vid": vid,
    }
    s3.stop()
    filer.close()
    vs.stop()
    master.stop()


def _drop_gateway_caches(gw):
    gw["filer"].chunk_cache.clear()
    cache = gw["vs"].store.ec_interval_cache
    if cache is not None:
        cache.clear()


def test_chaos_under_gateway_load(gateway_cluster):
    """Fault registry ARMED (one data shard dead + latency spikes on
    mounted shard reads) while 32 concurrent clients hammer GETs:
    every response must be byte-correct or a clean 503 — no hangs, no
    corrupt bodies. Caches dropped per burst so the data plane (and its
    fault points) stays exercised."""
    gw = gateway_cluster
    handle = faults.inject(
        "ec.volume.shard_read",
        faults.latency(0.02),
        when=faults.every(7),
    )
    counts = {"ok": 0, "unavailable": 0, "bad": 0}
    lock = threading.Lock()

    def client(i: int):
        sess = requests.Session()
        for j in range(3):
            if j == 0 and i % 8 == 0:
                _drop_gateway_caches(gw)  # keep misses flowing
            try:
                r = sess.get(f"{gw['base']}/load/obj", timeout=60)
            except Exception:
                with lock:
                    counts["bad"] += 1
                continue
            with lock:
                if r.status_code == 200 and r.content == gw["data"]:
                    counts["ok"] += 1
                elif r.status_code == 503:
                    counts["unavailable"] += 1  # clean backpressure
                else:
                    counts["bad"] += 1

    try:
        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(32)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        alive = [t for t in threads if t.is_alive()]
        assert not alive, f"{len(alive)} clients hung under chaos"
    finally:
        faults.REGISTRY.remove(handle)
    assert counts["bad"] == 0, counts
    assert counts["ok"] > 0, counts
    assert counts["ok"] + counts["unavailable"] == 32 * 3
    # serving traffic ran in the scheduler's FOREGROUND class
    snaps = gw["vs"].store.ec_scheduler.stats_snapshot()
    fg_admitted = sum(
        s["classes"]["foreground"]["admitted"] for s in snaps
    )
    assert fg_admitted > 0, snaps


def test_degraded_get_trace_shows_foreground_admission(gateway_cluster):
    """Span stage attribution proves the scheduler integration: a
    degraded GET's trace carries an ec.degraded_read span with an
    admission_wait stage (the foreground ticket's wait)."""
    gw = gateway_cluster
    trace.configure(
        enabled=True, ring_size=512,
        ring_spans=trace.DEFAULT_RING_SPANS, slow_op_s=0.0,
    )
    trace.reset()
    try:
        _drop_gateway_caches(gw)
        r = requests.get(f"{gw['base']}/load/obj", timeout=60)
        assert r.status_code == 200 and r.content == gw["data"]
        tid = r.headers.get(trace.TRACE_ID_HEADER)
        assert tid

        def walk(node):
            yield node
            for ch in node.get("children", ()):
                yield from walk(ch)

        stages = set()
        found_degraded = False
        for doc in trace.traces(tid):
            for node in walk(doc):
                if node["op"] == "ec.degraded_read":
                    found_degraded = True
                    stages.update(node["stages"])
        assert found_degraded, "degraded read must be in the GET's trace"
        assert "admission_wait" in stages, (
            f"foreground admission must be attributed in stages: {stages}"
        )
    finally:
        trace.configure(enabled=False)
        trace.reset()


def test_hot_cache_kills_miss_path_and_debug_gateway_surface(
    gateway_cluster,
):
    """With caches warm, repeated GETs stay off the reconstruction
    path (hot-cache hits climb, reconstructions don't), and the
    /debug/gateway surface exposes the counters + front-end state."""
    gw = gateway_cluster
    _drop_gateway_caches(gw)
    assert (
        requests.get(f"{gw['base']}/load/obj", timeout=60).content
        == gw["data"]
    )
    hits_before = gw["filer"].chunk_cache.hits
    loads_before = gw["filer"].chunk_cache.loads
    for _ in range(5):
        r = requests.get(f"{gw['base']}/load/obj", timeout=60)
        assert r.status_code == 200 and r.content == gw["data"]
    assert gw["filer"].chunk_cache.hits > hits_before
    assert gw["filer"].chunk_cache.loads == loads_before, (
        "warm GETs must not touch the chunk-fetch path"
    )
    # the SLO-adjacent surface on the volume server's status plane
    vs = gw["vs"]
    doc = requests.get(
        f"http://localhost:{vs.port}/debug/gateway", timeout=10
    ).json()
    assert doc["front_end"]["kind"] == "pooled"
    assert doc["front_end"]["workers"] > 0
    assert "filer_chunk" in doc["hot_cache"]
    assert doc["hot_cache"]["filer_chunk"]["hits"] > 0
    assert "ec_interval" in doc["hot_cache"]
    assert "inflight" in doc and "rejected" in doc
