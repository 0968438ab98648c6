"""Native network byte plane (ISSUE 12): shard net-plane egress/ingress
bit identity vs the Python plane, fused copy-in CRC verify-and-exclude,
mid-stream death and armed-chaos routing, the O_DIRECT sink fallback,
sendfile-vs-buffered HTTP body identity through a real PooledHTTPServer,
and the fastread loader's one-warning degrade.
"""

from __future__ import annotations

import os
import socket
import threading
import urllib.request

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import net_plane
from seaweedfs_tpu.ec.backend import CpuBackend
from seaweedfs_tpu.ec.bitrot import BitrotProtection, ShardChecksumBuilder
from seaweedfs_tpu.ec.context import ECContext, ECError
from seaweedfs_tpu.ec.peer_rebuild import (
    PeerFetchTransient,
    rebuild_from_peers,
    staging_dir,
)
from seaweedfs_tpu.utils import native
from seaweedfs_tpu.utils.crc import crc32c
from seaweedfs_tpu.utils.retry import RetryPolicy

CTX = ECContext(4, 2)
BLOCK = 4096
SHARD_SIZE = 3 * BLOCK + 57  # ragged: partial final granule on purpose

FAST = RetryPolicy(max_attempts=3, base_delay=0.0, max_delay=0.0, jitter=0.0)


# ------------------------------------------------------------ primitives


def test_sendv_recv_into_roundtrip_with_fused_crc():
    """Scatter-gather egress + direct-landing ingress are byte-exact,
    and the granule CRCs rolled DURING the copy-in match a separate
    CRC pass over the landed bytes."""
    a, b = socket.socketpair()
    try:
        parts = [
            b"x" * 3000,
            np.random.default_rng(0).integers(0, 256, 5000, dtype=np.uint8),
            memoryview(b"tail" * 25),
        ]
        total = sum(len(p) for p in parts)
        sent = native.sendv(a.fileno(), parts, timeout_ms=5000)
        assert sent == total
        dst = np.zeros(total, np.uint8)
        crc_state = np.zeros(1, np.uint32)
        filled = np.zeros(1, np.uint64)
        out_crcs = np.zeros(total // 1024 + 2, np.uint32)
        out_counts = np.zeros(1, np.int32)
        got = native.recv_into(
            b.fileno(), dst, total, timeout_ms=5000, granule=1024,
            crc_state=crc_state, filled_state=filled,
            out_crcs=out_crcs, out_counts=out_counts,
        )
        assert got == total
        ref = b"".join(bytes(p) for p in parts)
        assert dst.tobytes() == ref
        for i in range(int(out_counts[0])):
            assert int(out_crcs[i]) == crc32c(ref[i * 1024 : (i + 1) * 1024])
        tail = ref[int(out_counts[0]) * 1024 :]
        if tail:
            assert int(crc_state[0]) == crc32c(tail)
    finally:
        a.close()
        b.close()


def test_send_file_offset_and_eof_short(tmp_path):
    p = tmp_path / "f"
    payload = bytes(range(256)) * 8
    p.write_bytes(payload)
    fd = os.open(p, os.O_RDONLY)
    a, b = socket.socketpair()
    try:
        assert native.send_file(a.fileno(), fd, 100, 500, 5000) == 500
        assert b.recv(500, socket.MSG_WAITALL) == payload[100:600]
        # reading past EOF is a SHORT send, not an error (the torn-
        # stream contract the net plane inherits from the gRPC stream)
        sent = native.send_file(
            a.fileno(), fd, len(payload) - 10, 100, 5000
        )
        assert sent == 10
    finally:
        os.close(fd)
        a.close()
        b.close()


def test_recv_into_short_on_peer_close():
    a, b = socket.socketpair()
    a.sendall(b"abc")
    a.close()
    dst = np.zeros(10, np.uint8)
    got = native.recv_into(b.fileno(), dst, 10, timeout_ms=2000)
    b.close()
    assert got == 3 and dst[:3].tobytes() == b"abc"


# -------------------------------------------------------------- harness


def synth(tmp_path, local=(0, 1), seed=0, leaf=0, shard_size=SHARD_SIZE):
    """RS-consistent shard set + sidecar (v1 when leaf=0, v2 with a
    leaf level otherwise); only `local` shard files exist under
    tmp_path/local. Full copies live under tmp_path/peer (what the
    net-plane servers serve). Returns (base, peer_dir, blobs)."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (CTX.data_shards, shard_size), dtype=np.uint8)
    parity = CpuBackend(CTX).encode(data)
    shards = np.concatenate([data, parity], axis=0)
    blobs = {i: shards[i].tobytes() for i in range(CTX.total)}
    block = 4 * leaf if leaf else BLOCK  # v2: leaf must divide block
    builders = [ShardChecksumBuilder(block, leaf) for _ in range(CTX.total)]
    for i in range(CTX.total):
        builders[i].write(blobs[i])
    prot = BitrotProtection.from_builders(CTX, builders, generation=3)
    ldir = tmp_path / "local"
    pdir = tmp_path / "peer"
    ldir.mkdir(exist_ok=True)
    pdir.mkdir(exist_ok=True)
    base = str(ldir / "1")
    prot.save(base + ".ecsum")
    for i in local:
        with open(base + CTX.to_ext(i), "wb") as f:
            f.write(blobs[i])
    for i in range(CTX.total):
        with open(str(pdir / "1") + CTX.to_ext(i), "wb") as f:
            f.write(blobs[i])
    return base, str(pdir), blobs


class FilePlane:
    """A ShardNetPlane serving shard files out of a directory — the
    test stand-in for a peer volume server (generation fence included).
    """

    def __init__(self, directory, generation=3, plane_cls=None):
        self.directory = directory
        self.generation = generation
        self._fds: dict[int, int] = {}
        cls = plane_cls or net_plane.ShardNetPlane
        self.server = cls(
            "127.0.0.1", 0, self._resolve, server_label="test-peer"
        )
        self.server.start()
        self.addr = ("127.0.0.1", self.server.port)

    def _resolve(self, vid, sid, gen):
        if gen and gen != self.generation:
            raise net_plane.NetPlaneError("stale generation")
        fd = self._fds.get(sid)
        if fd is None:
            p = os.path.join(self.directory, f"{vid}" + CTX.to_ext(sid))
            if not os.path.exists(p):
                raise net_plane.NetPlaneError("shard not local")
            fd = os.open(p, os.O_RDONLY)
            self._fds[sid] = fd
        return fd, os.fstat(fd).st_size

    def close(self):
        self.server.stop()
        for fd in self._fds.values():
            os.close(fd)


@pytest.fixture
def planes_env():
    created = []

    def make(directory, **kw):
        fp = FilePlane(directory, **kw)
        created.append(fp)
        return fp

    clients = []

    def client():
        c = net_plane.NetPlaneClient(timeout=5.0, connect_timeout=1.0)
        clients.append(c)
        return c

    yield make, client
    for c in clients:
        c.close()
    for fp in created:
        fp.close()


def wire_transports(client, addr_by_peer, generation=3):
    """(fetch, fetch_into) pair over the SAME net-plane wire: fetch is
    the Python-plane bytes transport (also used for granule re-reads),
    fetch_into the native-plane landing transport."""

    def fetch(peer, sid, off, size):
        try:
            return client.read_bytes(
                addr_by_peer[peer], 1, sid, generation, off, size
            )
        except net_plane.NetPlaneUnavailable as e:
            raise PeerFetchTransient(str(e)) from e
        except net_plane.NetPlaneError as e:
            raise PeerFetchTransient(str(e)) from e

    fetch_into = net_plane.make_fetch_into(
        client, 1, generation, addr_of=lambda peer: addr_by_peer[peer]
    )
    return fetch, fetch_into


# ------------------------------------------------- bit identity (streams)


@pytest.mark.parametrize("leaf", [0, BLOCK])
def test_peer_rebuild_native_vs_python_bit_identical(
    tmp_path, monkeypatch, planes_env, leaf
):
    """The tentpole acceptance at test scale: a shard rebuilt from
    NATIVE-plane-fetched sources (sendfile egress -> recv-into pooled
    buffers, fused copy-in CRC) is byte-equal to one rebuilt from
    Python-plane fetches over the same wire, and both to the original.
    v1 and v2 sidecars, ragged tails, multi-chunk streams."""
    from seaweedfs_tpu.ec import peer_rebuild as pr

    from seaweedfs_tpu.utils import metrics as M

    def total(counter, plane=None):
        return sum(
            v for key, v in counter.snapshot().items()
            if plane is None or (key and key[0] == plane)
        )

    monkeypatch.setattr(pr, "FETCH_CHUNK", 8192)  # force multi-chunk
    make, client = planes_env
    results = {}
    for tag in ("native", "python"):
        sub = tmp_path / tag
        sub.mkdir()
        base, pdir, blobs = synth(sub, local=(0,), leaf=leaf, seed=11)
        fp = make(pdir)
        c = client()
        fetch, fetch_into = wire_transports(c, {"p": fp.addr})
        copied0 = total(M.net_bytes_copied_total)
        received0 = total(M.net_bytes_received_total, tag)
        rep = rebuild_from_peers(
            base,
            {1: ["p"], 2: ["p"], 3: ["p"], 4: ["p"]},
            fetch,
            ctx=CTX,
            targets=[5],
            backend=CpuBackend(CTX),
            policy=FAST,
            fetch_into=fetch_into if tag == "native" else None,
        )
        assert rep.rebuilt == [5]
        want_plane = tag
        assert set(rep.fetched_plane.values()) == {want_plane}
        # copies per byte served: the native plane lands every payload
        # byte without materializing it in a Python buffer, the Python
        # plane copies each at least once
        copied = total(M.net_bytes_copied_total) - copied0
        received = total(M.net_bytes_received_total, tag) - received0
        assert received >= len(rep.fetched) * SHARD_SIZE
        assert (copied == 0) if tag == "native" else (copied >= received)
        results[tag] = (
            open(base + CTX.to_ext(5), "rb").read(), blobs[5]
        )
    got_n, orig = results["native"]
    got_p, _ = results["python"]
    assert got_n == got_p == orig


def test_shard_range_reads_native_vs_python_and_generation_fence(
    tmp_path, planes_env
):
    """Client-level: read_into lands exactly the requested range with
    correct fused CRCs; read_bytes over the same wire is byte-equal; a
    stale generation is a clean protocol refusal on both."""
    make, client = planes_env
    base, pdir, blobs = synth(tmp_path, local=())
    fp = make(pdir)
    c = client()
    for off, size in ((0, SHARD_SIZE), (BLOCK, 2 * BLOCK), (17, 301)):
        dst = np.zeros(size, np.uint8)
        crcs = c.read_into(fp.addr, 1, 2, 3, off, size, dst, granule=BLOCK)
        ref = blobs[2][off : off + size]
        assert dst.tobytes() == ref
        for i, lo in enumerate(range(0, size, BLOCK)):
            assert int(crcs[i]) == crc32c(ref[lo : lo + BLOCK])
        assert c.read_bytes(fp.addr, 1, 2, 3, off, size) == ref
    with pytest.raises(net_plane.NetPlaneError, match="stale generation"):
        c.read_bytes(fp.addr, 1, 2, 999, 0, 16)


# ------------------------------------- a connection per in-flight read


def pooled(client, addr):
    with client._lock:
        return [s for s, _t in client._npool.get(addr, [])]


def test_two_range_reads_to_one_address_are_in_flight_at_once(tmp_path, planes_env):
    """Each `read_into` checks a connection out for itself: two to one
    holder meet INSIDE the holder's resolver, which lets neither go on
    before the other has arrived. A client that kept one socket per
    address would send the second only after the first had ended."""
    make, client = planes_env
    _base, pdir, blobs = synth(tmp_path, local=())
    fp = make(pdir)
    both_here = threading.Barrier(2, timeout=10)
    resolve = fp.server.resolve

    def meet(vid, sid, gen):
        try:
            both_here.wait()
        except threading.BrokenBarrierError:
            raise net_plane.NetPlaneError("the other read never came") from None
        return resolve(vid, sid, gen)

    fp.server.resolve = meet
    c = client()
    got: dict[int, object] = {}

    def read(sid):
        dst = np.zeros(SHARD_SIZE, np.uint8)
        try:
            crcs = c.read_into(fp.addr, 1, sid, 3, 0, SHARD_SIZE, dst, granule=BLOCK)
            got[sid] = (dst.tobytes(), [int(x) for x in crcs])
        except Exception as e:  # noqa: BLE001 - shown by the assertion below
            got[sid] = e

    threads = [threading.Thread(target=read, args=(sid,)) for sid in (2, 4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(20)
    for sid in (2, 4):
        assert not isinstance(got[sid], Exception), got[sid]
        body, crcs = got[sid]
        assert body == blobs[sid]
        assert crcs == [crc32c(blobs[sid][lo : lo + BLOCK]) for lo in range(0, SHARD_SIZE, BLOCK)]
    # both connections went back to the pool, and the next read takes one
    fp.server.resolve = resolve
    assert len(pooled(c, fp.addr)) == 2
    c.read_into(fp.addr, 1, 2, 3, 0, 64, np.zeros(64, np.uint8))
    assert len(pooled(c, fp.addr)) == 2


@pytest.mark.parametrize("how", ["refused", "short", "torn"])
def test_a_range_reads_connection_is_kept_after_a_refusal_and_closed_after_a_bad_stream(
    tmp_path, planes_env, how
):
    """A refusal leaves the stream in frame sync: its connection serves
    the next read. A range that comes short of what was asked, or stops
    half way, leaves bytes nobody will read: that connection is closed,
    and the read after it gets a fresh one and the right bytes."""
    make, client = planes_env
    _base, pdir, blobs = synth(tmp_path, local=())
    fp = make(pdir, plane_cls=TruncatingPlane if how == "torn" else None)
    c = client()
    dst = np.zeros(64, np.uint8)
    with pytest.raises(net_plane.NetPlaneError) as refused:
        if how == "refused":
            c.read_into(fp.addr, 1, 2, 999, 0, 64, dst)  # a stale generation
        elif how == "short":
            c.read_into(fp.addr, 1, 2, 3, SHARD_SIZE - 10, 64, dst)  # past the end
        else:
            c.read_into(fp.addr, 1, 2, 3, 0, 64, dst)
    assert {"refused": "stale generation", "short": "short stream", "torn": "torn stream"}[
        how
    ] in str(refused.value)
    assert len(pooled(c, fp.addr)) == (1 if how == "refused" else 0)
    if how == "torn":
        fp.server._serve_one = net_plane.ShardNetPlane._serve_one.__get__(fp.server)
    c.read_into(fp.addr, 1, 2, 3, 7, 64, dst)
    assert dst.tobytes() == blobs[2][7:71]
    assert len(pooled(c, fp.addr)) == 1


def test_a_whole_shard_fetch_and_a_bytes_read_use_the_pool_too(tmp_path, planes_env):
    make, client = planes_env
    _base, pdir, blobs = synth(tmp_path, local=())
    fp = make(pdir)
    c = client()
    out = tmp_path / "copy"
    with open(out, "wb") as f:
        assert c.fetch_shard_to_file(fp.addr, 1, 5, 3, f, chunk=BLOCK) == SHARD_SIZE
    assert out.read_bytes() == blobs[5]
    (kept,) = pooled(c, fp.addr)
    assert c.read_bytes(fp.addr, 1, 5, 3, 9, 100) == blobs[5][9:109]
    assert pooled(c, fp.addr) == [kept]
    assert not hasattr(c, "_conns") and not hasattr(c, "_addr_lock")


def test_the_planes_connection_threads_are_named_and_its_egress_sums_are_exact(
    tmp_path, planes_env
):
    """Eight readers at one plane, 40 reads each: every connection
    thread carries the plane's prefix (the wait probes class it by
    that), and `sendfile_bytes` + `python_bytes`, summed under a lock,
    is to the byte what the readers landed."""
    from seaweedfs_tpu.utils import interp_probe

    make, client = planes_env
    _base, pdir, blobs = synth(tmp_path, local=())
    fp = make(pdir)
    c = client()
    landed = [0] * 8

    def read(w):
        dst = np.zeros(SHARD_SIZE, np.uint8)
        for n in range(40):
            size = 1 + (w * 131 + n * 17) % SHARD_SIZE
            c.read_into(fp.addr, 1, (w + n) % CTX.total, 3, 0, size, dst)
            landed[w] += size

    import sys

    threads = [threading.Thread(target=read, args=(w,)) for w in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)  # a lost update needs a switch between read and write
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    serving = [t for t in threading.enumerate()
               if t.name == f"{net_plane.CONN_THREAD_PREFIX}{fp.server.port}"]
    assert 1 <= len(serving) <= 8  # a connection, and its thread, per reader at most
    assert {interp_probe.thread_class(t) for t in serving} == {"shard_plane"}
    st = fp.server
    _settle(lambda: st.sendfile_bytes + st.python_bytes == sum(landed))
    assert st.sendfile_bytes + st.python_bytes == sum(landed)


# ------------------------------------------ chaos on the native ingress


class TruncatingPlane(net_plane.ShardNetPlane):
    """Advertises the full length, ships half the bytes, then kills the
    connection — a peer dying mid-sendfile."""

    def _serve_one(self, conn, vid, sid, gen, off, size):
        fd, fsize = self.resolve(vid, sid, gen)
        n = max(0, min(size, fsize - off))
        conn.sendall(net_plane._RESP.pack(0, n))
        conn.sendall(os.pread(fd, n // 2, off))
        return False


def test_native_ingress_mid_stream_death_no_partial_admit(
    tmp_path, planes_env
):
    """Mid-stream peer death on the native path: every attempt lands
    short, the holder is abandoned after retries, and with <k sources
    the rebuild REFUSES cleanly — staging wiped, no canonical shard
    file ever appears (no partial admit)."""
    make, client = planes_env
    base, pdir, blobs = synth(tmp_path, local=(0, 1))
    fp = make(pdir, plane_cls=TruncatingPlane)
    c = client()

    def fetch(peer, sid, off, size):  # peer is truly dead to python too
        raise PeerFetchTransient("peer down")

    fetch_into = net_plane.make_fetch_into(
        c, 1, 3, addr_of=lambda peer: fp.addr
    )
    with pytest.raises(ECError, match="refusing"):
        rebuild_from_peers(
            base,
            {2: ["p"], 3: ["p"]},
            fetch,
            ctx=CTX,
            targets=[5],
            backend=CpuBackend(CTX),
            policy=FAST,
            fetch_into=fetch_into,
        )
    assert not os.path.exists(base + CTX.to_ext(5))
    assert not os.path.exists(staging_dir(base))


def test_native_fused_crc_excludes_rotten_peer_and_replans(
    tmp_path, planes_env
):
    """A peer serving rot is caught by the COPY-IN CRCs (no extra byte
    pass), re-read once at granule width to rule out wire corruption,
    then excluded — and the plan re-routes to a clean holder. The
    rebuilt shard is still byte-exact."""
    make, client = planes_env
    base, pdir, blobs = synth(tmp_path, local=(0,))
    # rotten copy: same shards, one flipped byte mid-shard in shard 2
    rdir = tmp_path / "rot"
    rdir.mkdir()
    for i in range(CTX.total):
        blob = bytearray(blobs[i])
        if i == 2:
            blob[BLOCK + 17] ^= 0xFF
        with open(str(rdir / "1") + CTX.to_ext(i), "wb") as f:
            f.write(bytes(blob))
    bad = make(str(rdir))
    good = make(pdir)
    c = client()
    addr_by_peer = {"bad": bad.addr, "good": good.addr}
    fetch, fetch_into = wire_transports(c, addr_by_peer)
    rep = rebuild_from_peers(
        base,
        {1: ["good"], 2: ["bad", "good"], 3: ["good"], 4: ["good"]},
        fetch,
        ctx=CTX,
        targets=[5],
        backend=CpuBackend(CTX),
        policy=FAST,
        fetch_into=fetch_into,
    )
    assert rep.rebuilt == [5]
    assert "bad" in rep.excluded_peers
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_armed_chaos_routes_python_plane_bit_identical(
    tmp_path, planes_env
):
    """The armed-registry contract: with latency chaos armed, streams
    route through the Python plane even though fetch_into is wired (the
    byte-mutating seams need materialized bytes), and the result is
    byte-identical."""
    make, client = planes_env
    base, pdir, blobs = synth(tmp_path, local=(0,))
    fp = make(pdir)
    c = client()
    fetch, fetch_into = wire_transports(c, {"p": fp.addr})
    with faults.injected(
        "ec.peer_fetch.read", faults.latency(0.001), when=faults.every(3)
    ):
        rep = rebuild_from_peers(
            base,
            {1: ["p"], 2: ["p"], 3: ["p"]},
            fetch,
            ctx=CTX,
            targets=[5],
            backend=CpuBackend(CTX),
            policy=FAST,
            fetch_into=fetch_into,
        )
    assert rep.rebuilt == [5]
    assert set(rep.fetched_plane.values()) == {"python"}
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_peer_without_plane_falls_back_to_python_fetch(
    tmp_path, planes_env
):
    """A peer whose net-plane port refuses is a capability miss, not a
    failure: the stream rides the Python fetch, the rebuild succeeds,
    and the refusal is memoized (one connect attempt per peer)."""
    make, client = planes_env
    base, pdir, blobs = synth(tmp_path, local=(0,))
    fp = make(pdir)
    c = client()
    # plane address points at a dead port; python fetch uses the live one
    dead = ("127.0.0.1", 1)  # port 1: connect refused
    fetch, _ = wire_transports(c, {"p": fp.addr})
    fetch_into = net_plane.make_fetch_into(
        c, 1, 3, addr_of=lambda peer: dead
    )
    rep = rebuild_from_peers(
        base,
        {1: ["p"], 2: ["p"], 3: ["p"]},
        fetch,
        ctx=CTX,
        targets=[5],
        backend=CpuBackend(CTX),
        policy=FAST,
        fetch_into=fetch_into,
    )
    assert rep.rebuilt == [5]
    assert set(rep.fetched_plane.values()) == {"python"}
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_ec_native_disabled_skips_native_plane(
    tmp_path, planes_env, monkeypatch
):
    """SEAWEED_EC_NATIVE=0 forces the pure-Python plane end to end even
    with a live net plane and fetch_into wired."""
    monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
    make, client = planes_env
    base, pdir, blobs = synth(tmp_path, local=(0,))
    fp = make(pdir)
    c = client()
    fetch, fetch_into = wire_transports(c, {"p": fp.addr})
    rep = rebuild_from_peers(
        base,
        {1: ["p"], 2: ["p"], 3: ["p"]},
        fetch,
        ctx=CTX,
        targets=[5],
        backend=CpuBackend(CTX),
        policy=FAST,
        fetch_into=fetch_into,
    )
    assert set(rep.fetched_plane.values()) == {"python"}
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


# ----------------------------------------------------- O_DIRECT fallback


def test_odirect_sink_misaligned_tail_falls_back_bit_identical(
    tmp_path, monkeypatch
):
    """SEAWEED_EC_ODIRECT=1: aligned batches may ride O_DIRECT, the
    misaligned ragged tail transparently drops to buffered, and the
    bytes + BOTH sidecar CRC levels stay identical to the Python
    sink."""
    monkeypatch.setenv("SEAWEED_EC_ODIRECT", "1")
    from seaweedfs_tpu.ec.native_io import aligned_matrix
    from seaweedfs_tpu.ec.pipeline import FusedShardSink, PyShardSink

    widths = [4096 * 4, 4096 * 2, 1234]  # aligned, aligned, ragged tail
    batches = [
        np.random.default_rng(50 + i).integers(0, 256, (3, w), dtype=np.uint8)
        for i, w in enumerate(widths)
    ]
    out = {}
    for tag, cls in (("fused", FusedShardSink), ("py", PyShardSink)):
        files = [open(tmp_path / f"{tag}{i}", "w+b") for i in range(3)]
        sink = cls(files, block_size=8192, leaf_size=4096)
        for i, w in enumerate(widths):
            m = aligned_matrix(3, w)
            m[:] = batches[i]
            sink.append_rows([m[j] for j in range(3)])
        crcs, leaves = sink.block_crcs(), sink.leaf_crcs()
        if tag == "fused":
            # whatever the fs decided, the ragged tail must have
            # dropped O_DIRECT for every shard by stream end
            assert not sink.direct_flags().any()
        for f in files:
            f.flush()
            f.close()
        out[tag] = (
            [open(tmp_path / f"{tag}{i}", "rb").read() for i in range(3)],
            crcs,
            leaves,
        )
    assert out["fused"] == out["py"]


def test_odirect_encode_end_to_end_bit_identical(tmp_path, monkeypatch):
    """Full encode with the O_DIRECT knob on vs off: identical shard
    files and sidecar."""
    from seaweedfs_tpu.ec.encoder import write_ec_files

    rng = np.random.default_rng(9)
    payload = rng.integers(0, 256, 3 * 65536 + 999, dtype=np.uint8).tobytes()
    outs = {}
    for tag, flag in (("on", "1"), ("off", "0")):
        monkeypatch.setenv("SEAWEED_EC_ODIRECT", flag)
        d = tmp_path / tag
        d.mkdir()
        base = str(d / "1")
        with open(base + ".dat", "wb") as f:
            f.write(payload)
        write_ec_files(base, ctx=CTX, backend=CpuBackend(CTX))
        outs[tag] = {
            ext: open(base + ext, "rb").read()
            for ext in [CTX.to_ext(i) for i in range(CTX.total)]
        }
    assert outs["on"] == outs["off"]


# ------------------------------------------------ HTTP sendfile egress


def test_pooled_http_get_native_vs_buffered_byte_identity(monkeypatch):
    """The warm-gateway egress contract: a GET served through
    send_body's native scatter-gather sender is byte-identical to the
    SEAWEED_EC_NATIVE=0 wfile path, through a REAL PooledHTTPServer,
    and the native byte counter moves only on the native run."""
    from http.server import BaseHTTPRequestHandler

    from seaweedfs_tpu.utils import metrics as M
    from seaweedfs_tpu.utils.http_pool import PooledHTTPServer, send_body

    body = os.urandom(200 * 1024)

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            send_body(self, body)

    srv = PooledHTTPServer(("127.0.0.1", 0), H, workers=2)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        port = srv.socket.getsockname()[1]
        url = f"http://127.0.0.1:{port}/x"
        def delta(snap0, plane_name):
            cur = dict(M.net_bytes_sent_total.snapshot())
            return cur.get((plane_name, "read"), 0) - snap0.get(
                (plane_name, "read"), 0
            )

        before = dict(M.net_bytes_sent_total.snapshot())
        got_native = urllib.request.urlopen(url, timeout=10).read()
        assert _settle(lambda: delta(before, "native") == len(body))
        mid = dict(M.net_bytes_sent_total.snapshot())
        monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
        got_python = urllib.request.urlopen(url, timeout=10).read()
        assert got_native == got_python == body
        assert _settle(lambda: delta(mid, "python") == len(body))
    finally:
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------- fastread loader gate


def test_fastread_failed_make_degrades_with_one_attempt(tmp_path, monkeypatch):
    """A failed sidecar build is cached: ImportError every call, make
    runs ONCE — the degrade is one warning, not per-call log spam."""
    from seaweedfs_tpu.utils import fastread

    bad = tmp_path / "native"
    bad.mkdir()
    (bad / "Makefile").write_text("all:\n\tfalse\n")
    (bad / "fastread.cpp").write_text("// never compiles via this Makefile")
    monkeypatch.setattr(fastread, "_NATIVE_DIR", str(bad))
    monkeypatch.setattr(fastread, "_lib", None)
    monkeypatch.setattr(fastread, "_lib_err", None)
    calls = []
    real_run = fastread.subprocess.run

    def counting_run(*a, **kw):
        calls.append(a)
        return real_run(*a, **kw)

    monkeypatch.setattr(fastread.subprocess, "run", counting_run)
    with pytest.raises(ImportError):
        fastread.lib()
    with pytest.raises(ImportError):
        fastread.lib()
    assert len(calls) == 1


def test_fastread_stale_on_shared_header_change(tmp_path, monkeypatch):
    """The sidecar shares sn_net.h with the core: a header newer than
    the .so must trigger a rebuild (the PR 10-era loader only checked
    existence and would happily serve a stale ABI)."""
    from seaweedfs_tpu.utils import fastread

    d = tmp_path / "native"
    d.mkdir()
    so = d / "libseaweed_fastread.so"
    so.write_bytes(b"x")
    (d / "fastread.cpp").write_text("//")
    (d / "sn_net.h").write_text("//")
    monkeypatch.setattr(fastread, "_NATIVE_DIR", str(d))
    old = os.path.getmtime(so)
    for p in (d / "fastread.cpp", d / "sn_net.h"):
        os.utime(p, (old - 5, old - 5))
    os.utime(d / "Makefile", (old - 5, old - 5)) if (
        d / "Makefile"
    ).exists() else None
    assert not fastread._stale(str(so))
    os.utime(d / "sn_net.h", (old + 5, old + 5))
    assert fastread._stale(str(so))


# ------------------------------------------- needle/chunk opcode (ISSUE 13)
# The warm gateway path's filer->volume chunk fetch over the same
# sidecar: whole-needle payloads spliced with sendfile, landed in
# pooled aligned buffers with the CRC fused into the copy-in.


def _settle(fn, timeout=5.0):
    """Egress byte counters land AFTER the last payload byte is on the
    wire, so a fast client can observe the full body before the serving
    thread runs its bookkeeping — poll briefly instead of racing it."""
    import time as _time

    deadline = _time.monotonic() + timeout
    while not fn() and _time.monotonic() < deadline:
        _time.sleep(0.005)
    return fn()


def _refuse_shards(vid, sid, gen):
    raise net_plane.NetPlaneError("no shards here")


def _needle_plane(tmp_path, payload, crc=None, resolve=None):
    p = tmp_path / "needle.dat"
    p.write_bytes(b"HDR!" + payload + b"TRAILER")
    want = crc32c(payload) if crc is None else crc

    def resolve_needle(vid, nid, cookie):
        assert (vid, nid, cookie) == (7, 0xABC, 0x55)
        fd = os.open(p, os.O_RDONLY)
        return fd, 4, len(payload), want, True

    srv = net_plane.ShardNetPlane(
        "127.0.0.1", 0, _refuse_shards,
        resolve_needle=resolve if resolve is not None else resolve_needle,
        server_label="needle-test",
    )
    srv.start()
    return srv


@pytest.mark.parametrize("plane", ["native", "python"])
def test_needle_read_roundtrip(tmp_path, monkeypatch, plane):
    """Whole-needle fetch over the chunk-read opcode is byte-exact on
    both landing planes, and the server counts the egress on the right
    plane (sendfile for native, pread+sendall for python)."""
    if plane == "python":
        monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
    payload = np.random.default_rng(3).integers(
        0, 256, 300_000, dtype=np.uint8
    ).tobytes()
    srv = _needle_plane(tmp_path, payload)
    client = net_plane.NetPlaneClient()
    try:
        got = client.read_needle(
            ("127.0.0.1", srv.port), 7, 0xABC, 0x55
        )
        assert got == payload
        assert srv.needle_requests == 1
        if plane == "native":
            assert _settle(lambda: srv.sendfile_bytes == len(payload))
            assert srv.python_bytes == 0
        else:
            assert _settle(lambda: srv.python_bytes == len(payload))
            assert srv.sendfile_bytes == 0
        # second read reuses the pooled connection
        assert client.read_needle(
            ("127.0.0.1", srv.port), 7, 0xABC, 0x55
        ) == payload
    finally:
        client.close()
        srv.stop()


def test_needle_read_crc_mismatch_refused(tmp_path):
    """A stored CRC that doesn't match the landed bytes (vacuum racing
    the locate, stale fd) surfaces as NetPlaneError — the caller falls
    back to the locked HTTP path — never as silent wrong bytes."""
    payload = b"q" * 70_000
    srv = _needle_plane(tmp_path, payload, crc=crc32c(payload) ^ 0xDEAD)
    client = net_plane.NetPlaneClient()
    try:
        with pytest.raises(net_plane.NetPlaneError, match="CRC mismatch"):
            client.read_needle(("127.0.0.1", srv.port), 7, 0xABC, 0x55)
    finally:
        client.close()
        srv.stop()


def test_needle_read_refusal_message(tmp_path):
    """Resolver refusals (not here / EC / TTL'd / cookie mismatch)
    travel as protocol errors with the message intact."""

    def refuse(vid, nid, cookie):
        raise net_plane.NetPlaneError("volume not here (or EC)")

    srv = _needle_plane(tmp_path, b"", resolve=refuse)
    client = net_plane.NetPlaneClient()
    try:
        with pytest.raises(net_plane.NetPlaneError, match="not here"):
            client.read_needle(("127.0.0.1", srv.port), 7, 0xABC, 0x55)
        # the connection survives a refusal: shard opcode still works
        with pytest.raises(net_plane.NetPlaneError, match="no shards"):
            client.read_bytes(("127.0.0.1", srv.port), 1, 0, 0, 0, 10)
    finally:
        client.close()
        srv.stop()


def test_needle_read_refused_when_faults_armed(tmp_path):
    """An ARMED registry refuses needle serving outright: byte-mutating
    chaos belongs to the Python-HTTP path's storage fault points, so
    the client's fallback (HTTP) is the chaos surface."""
    payload = b"z" * 10_000
    srv = _needle_plane(tmp_path, payload)
    client = net_plane.NetPlaneClient()
    try:
        with faults.injected(
            "unrelated.point", faults.latency(0.0), when=faults.always()
        ):
            assert faults.active()
            with pytest.raises(
                net_plane.NetPlaneError, match="registry armed"
            ):
                client.read_needle(("127.0.0.1", srv.port), 7, 0xABC, 0x55)
        # disarmed again: served
        assert client.read_needle(
            ("127.0.0.1", srv.port), 7, 0xABC, 0x55
        ) == payload
    finally:
        client.close()
        srv.stop()


def test_no_plane_memo_ttl_revival(tmp_path):
    """ISSUE 13 satellite: the peer-without-plane memo must NOT be
    forever — a sidecar that comes up later (late boot, rolling
    restart) is re-probed after the TTL and re-adopted."""
    import time as _time

    hold = socket.socket()
    hold.bind(("127.0.0.1", 0))
    port = hold.getsockname()[1]
    hold.close()  # nothing listens here now
    client = net_plane.NetPlaneClient(unavailable_ttl=0.3)
    payload = b"revive" * 1000
    try:
        with pytest.raises(net_plane.NetPlaneUnavailable):
            client.read_needle(("127.0.0.1", port), 7, 0xABC, 0x55)
        # memoized: immediate retry refuses without a connect
        with pytest.raises(net_plane.NetPlaneUnavailable):
            client.read_needle(("127.0.0.1", port), 7, 0xABC, 0x55)
        p = tmp_path / "needle.dat"
        p.write_bytes(b"HDR!" + payload + b"TRAILER")

        def resolve_needle(vid, nid, cookie):
            fd = os.open(p, os.O_RDONLY)
            return fd, 4, len(payload), crc32c(payload), True

        srv = net_plane.ShardNetPlane(
            "127.0.0.1", port, _refuse_shards,
            resolve_needle=resolve_needle,
        )
        srv.start()
        try:
            _time.sleep(0.35)  # past the TTL: the revived peer re-probes
            assert client.read_needle(
                ("127.0.0.1", port), 7, 0xABC, 0x55
            ) == payload
        finally:
            srv.stop()
    finally:
        client.close()


def test_no_plane_reset_hook(tmp_path):
    """reset() drops the memo immediately — no TTL wait."""
    hold = socket.socket()
    hold.bind(("127.0.0.1", 0))
    port = hold.getsockname()[1]
    hold.close()
    client = net_plane.NetPlaneClient(unavailable_ttl=3600.0)
    payload = b"rst" * 500
    try:
        with pytest.raises(net_plane.NetPlaneUnavailable):
            client.read_needle(("127.0.0.1", port), 7, 0xABC, 0x55)
        p = tmp_path / "needle.dat"
        p.write_bytes(b"HDR!" + payload + b"TRAILER")

        def resolve_needle(vid, nid, cookie):
            fd = os.open(p, os.O_RDONLY)
            return fd, 4, len(payload), crc32c(payload), True

        srv = net_plane.ShardNetPlane(
            "127.0.0.1", port, _refuse_shards,
            resolve_needle=resolve_needle,
        )
        srv.start()
        try:
            # hour-long TTL: still refused from the memo...
            with pytest.raises(net_plane.NetPlaneUnavailable):
                client.read_needle(("127.0.0.1", port), 7, 0xABC, 0x55)
            client.reset(("127.0.0.1", port))
            # ...until the operator hook clears it
            assert client.read_needle(
                ("127.0.0.1", port), 7, 0xABC, 0x55
            ) == payload
        finally:
            srv.stop()
    finally:
        client.close()


def test_recv_overlap_env_gate():
    """ISSUE 13 satellite: the overlapped recv+CRC core gate
    (>=4 hardware threads) is env-tunable for the multi-core
    re-measure recipe; the 256 KiB size floor always applies."""
    prev = os.environ.get("SEAWEED_EC_NET_OVERLAP")
    try:
        os.environ["SEAWEED_EC_NET_OVERLAP"] = "1"
        assert native.recv_overlap_active(1 << 20) is True
        assert native.recv_overlap_active(4096) is False  # size floor
        os.environ["SEAWEED_EC_NET_OVERLAP"] = "0"
        assert native.recv_overlap_active(1 << 20) is False
        os.environ.pop("SEAWEED_EC_NET_OVERLAP")
        auto = native.recv_overlap_active(1 << 20)
        assert auto is ((os.cpu_count() or 1) >= 4)
    finally:
        if prev is None:
            os.environ.pop("SEAWEED_EC_NET_OVERLAP", None)
        else:
            os.environ["SEAWEED_EC_NET_OVERLAP"] = prev


def test_overlap_forced_on_is_bit_identical():
    """Forcing the overlapped core on a small host must stay byte- and
    CRC-exact (it is a scheduling change, not a data-path change)."""
    prev = os.environ.get("SEAWEED_EC_NET_OVERLAP")
    a, b = socket.socketpair()
    try:
        os.environ["SEAWEED_EC_NET_OVERLAP"] = "1"
        payload = np.random.default_rng(9).integers(
            0, 256, 512 * 1024, dtype=np.uint8
        ).tobytes()

        def send():
            a.sendall(payload)

        t = threading.Thread(target=send)
        t.start()
        dst = np.zeros(len(payload), np.uint8)
        crc_state = np.zeros(1, np.uint32)
        filled = np.zeros(1, np.uint64)
        out_crcs = np.zeros(len(payload) // 65536 + 2, np.uint32)
        out_counts = np.zeros(1, np.int32)
        got = native.recv_into(
            b.fileno(), dst, len(payload), timeout_ms=10000,
            granule=65536, crc_state=crc_state, filled_state=filled,
            out_crcs=out_crcs, out_counts=out_counts,
        )
        t.join()
        assert got == len(payload)
        assert dst.tobytes() == payload
        for i in range(int(out_counts[0])):
            assert int(out_crcs[i]) == crc32c(
                payload[i * 65536 : (i + 1) * 65536]
            )
    finally:
        if prev is None:
            os.environ.pop("SEAWEED_EC_NET_OVERLAP", None)
        else:
            os.environ["SEAWEED_EC_NET_OVERLAP"] = prev
        a.close()
        b.close()


# -------------------------------------------- O_DIRECT on a real block fs
# ROADMAP carried item (d): this box's overlay/9p/tmpfs all reject or
# bypass O_DIRECT, so engagement (direct_flags()==1 through an aligned
# stream) could never be asserted here. Point
# SEAWEED_TEST_BLOCK_FS_DIR at a writable directory on a real
# block-backed filesystem (ext4/xfs/btrfs) to run the positive test.

_NO_DIRECT_FS = {
    "overlay", "9p", "tmpfs", "ramfs", "nfs", "nfs4", "fuse", "zfs",
}


def _fs_type(path: str) -> str:
    """Filesystem type serving `path` (longest /proc/mounts prefix)."""
    best, best_type = "", "unknown"
    try:
        with open("/proc/mounts") as f:
            for line in f:
                parts = line.split()
                if len(parts) >= 3 and path.startswith(parts[1]) and len(
                    parts[1]
                ) > len(best):
                    best, best_type = parts[1], parts[2]
    except OSError:
        pass
    return best_type


def test_odirect_engages_on_block_fs(monkeypatch):
    """On a real block-backed fs, an all-aligned stream must KEEP
    O_DIRECT on every shard fd end to end — the page-cache bypass
    actually engages instead of silently degrading to buffered."""
    target = os.environ.get("SEAWEED_TEST_BLOCK_FS_DIR", "")
    if not target:
        pytest.skip("SEAWEED_TEST_BLOCK_FS_DIR not set")
    fs = _fs_type(os.path.abspath(target))
    if fs in _NO_DIRECT_FS:
        pytest.skip(f"{target} is {fs}: O_DIRECT unsupported/bypassed")
    monkeypatch.setenv("SEAWEED_EC_ODIRECT", "1")
    import tempfile

    from seaweedfs_tpu.ec.native_io import aligned_matrix
    from seaweedfs_tpu.ec.pipeline import FusedShardSink

    with tempfile.TemporaryDirectory(dir=target) as d:
        widths = [4096 * 4, 4096 * 2, 4096]  # every batch 4096-aligned
        batches = [
            np.random.default_rng(70 + i).integers(
                0, 256, (3, w), dtype=np.uint8
            )
            for i, w in enumerate(widths)
        ]
        files = [open(os.path.join(d, f"s{i}"), "w+b") for i in range(3)]
        try:
            sink = FusedShardSink(files, block_size=8192, leaf_size=4096)
            for i, w in enumerate(widths):
                m = aligned_matrix(3, w)
                m[:] = batches[i]
                sink.append_rows([m[j] for j in range(3)])
                # an aligned stream must never drop to buffered
                assert sink.direct_flags().all(), (
                    f"O_DIRECT dropped mid-stream on {fs} after width {w}"
                )
            ref = np.concatenate(batches, axis=1)
            for i, f in enumerate(files):
                f.flush()
                with open(f.name, "rb") as rf:
                    assert rf.read() == ref[i].tobytes()
        finally:
            for f in files:
                f.close()


def test_needle_reads_fan_out_concurrently(tmp_path):
    """Warm GETs arrive from N HTTP workers: needle reads check OUT a
    connection per in-flight request (no one-socket serialization),
    every reader gets byte-exact payload, and the pool is bounded."""
    payload = np.random.default_rng(5).integers(
        0, 256, 120_000, dtype=np.uint8
    ).tobytes()
    srv = _needle_plane(tmp_path, payload)
    client = net_plane.NetPlaneClient()
    errs: list = []

    def rd():
        try:
            assert client.read_needle(
                ("127.0.0.1", srv.port), 7, 0xABC, 0x55
            ) == payload
        except Exception as e:  # pragma: no cover - fails the assert
            errs.append(e)

    try:
        threads = [threading.Thread(target=rd) for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert srv.needle_requests == 12
        with client._lock:
            pooled = sum(len(v) for v in client._npool.values())
        assert 1 <= pooled <= client._npool_max
    finally:
        client.close()
        srv.stop()


def test_needle_pool_discards_idle_connections(tmp_path):
    """A pooled connection parked past the idle TTL is discarded at
    checkout (the server reaps idle peers at its request timeout) —
    the next GET dials fresh instead of burning its fast path on a
    dead socket."""
    payload = b"idle" * 2000
    srv = _needle_plane(tmp_path, payload)
    client = net_plane.NetPlaneClient()
    client._npool_idle_s = 0.05
    addr = ("127.0.0.1", srv.port)
    try:
        assert client.read_needle(addr, 7, 0xABC, 0x55) == payload
        # simulate the server reaping the parked conn while idle
        with client._lock:
            for s, _t in client._npool.get(addr, []):
                s.close()
        import time as _time

        _time.sleep(0.1)  # past the idle TTL: checkout must discard
        assert client.read_needle(addr, 7, 0xABC, 0x55) == payload
    finally:
        client.close()
        srv.stop()


def test_operations_negative_caches_volume_refusals(tmp_path):
    """A VOLUME-level plane refusal (EC/TTL'd/tiered) is negative-
    cached per vid: later chunk reads skip the refusal round trip and
    go straight to HTTP until the TTL expires."""
    import time as _time

    from seaweedfs_tpu.client.operations import Operations
    from seaweedfs_tpu.storage.file_id import FileId

    def refuse(vid, nid, cookie):
        raise net_plane.NetPlaneVolumeRefusal("volume not here (or EC)")

    srv = _needle_plane(tmp_path, b"", resolve=refuse)
    assert srv.port > 11023  # derive_port(g) must not wrap below
    ops = Operations(master="localhost:1")
    try:
        loc = type(
            "Loc", (), {"url": "127.0.0.1:80",
                        "grpc_port": srv.port - 10000}
        )()
        f = FileId(9, 0xABC, 0x55)
        assert ops._try_plane_read(loc, f) is None
        first = srv.requests
        assert first >= 1
        # negative-cached: no further round trips for this volume
        assert ops._try_plane_read(loc, f) is None
        assert srv.requests == first
        assert 9 in ops._plane_refused
        # TTL expiry re-probes (the volume may have converted back)
        ops._plane_refused[9] = _time.monotonic() - 3600
        assert ops._try_plane_read(loc, f) is None
        assert srv.requests == first + 1
    finally:
        ops.close()
        srv.stop()


def test_needle_level_refusal_not_negative_cached(tmp_path):
    """Per-needle refusals (not found / cookie mismatch, status 1) must
    NOT poison the per-volume negative cache — other needles on the
    volume may serve fine."""
    from seaweedfs_tpu.client.operations import Operations
    from seaweedfs_tpu.storage.file_id import FileId

    def refuse(vid, nid, cookie):
        raise net_plane.NetPlaneError("needle abc not found")

    srv = _needle_plane(tmp_path, b"", resolve=refuse)
    assert srv.port > 11023
    ops = Operations(master="localhost:1")
    try:
        loc = type(
            "Loc", (), {"url": "127.0.0.1:80",
                        "grpc_port": srv.port - 10000}
        )()
        assert ops._try_plane_read(loc, FileId(9, 0xABC, 0x55)) is None
        assert 9 not in ops._plane_refused
        # the plane is re-probed for the next needle on the volume
        first = srv.requests
        assert ops._try_plane_read(loc, FileId(9, 0xDEF, 0x55)) is None
        assert srv.requests == first + 1
    finally:
        ops.close()
        srv.stop()


# ------------------------------------------ needle write opcode (ISSUE 18)
# The PUT path's native twin: client header + payload on a pooled
# connection, server lands into pooled buffers (CRC fused into the
# copy-in), resolver appends to the volume, ACK carries the STORED CRC.


def _write_plane(resolve_write=None, resolve_blob=None):
    srv = net_plane.ShardNetPlane(
        "127.0.0.1", 0, _refuse_shards,
        resolve_write=resolve_write, resolve_blob=resolve_blob,
        server_label="write-test",
    )
    srv.start()
    return srv


@pytest.mark.parametrize("plane", ["native", "python"])
def test_needle_write_roundtrip(monkeypatch, plane):
    """One needle over the write opcode on both landing planes: the
    resolver sees the exact payload + meta, the ACK certifies the
    stored CRC, and the server counts ingress on the right plane.
    Ragged payload (not a granule multiple) exercises the fused CRC's
    tail path."""
    if plane == "python":
        monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
    payload = np.random.default_rng(7).integers(
        0, 256, 300_001, dtype=np.uint8
    ).tobytes()
    stored = {}

    def resolve_write(vid, nid, cookie, data, md):
        stored[(vid, nid)] = (cookie, data, dict(md))
        return len(data), crc32c(data)

    srv = _write_plane(resolve_write)
    client = net_plane.NetPlaneClient()
    try:
        size, crc = client.write_needle(
            ("127.0.0.1", srv.port), 7, 0xABC, 0x55, payload,
            name=b"f.bin", mime=b"application/x-test", fsync=True,
        )
        assert size == len(payload) and crc == crc32c(payload)
        cookie, data, md = stored[(7, 0xABC)]
        assert cookie == 0x55 and data == payload
        assert md["x-sw-w-fsync"] == "1"
        assert net_plane._unb64(md["x-sw-w-name"]) == b"f.bin"
        assert net_plane._unb64(md["x-sw-w-mime"]) == b"application/x-test"
        assert srv.write_requests == 1
        if plane == "native":
            assert srv.write_native_bytes == len(payload)
            assert srv.write_python_bytes == 0
        else:
            assert srv.write_python_bytes == len(payload)
            assert srv.write_native_bytes == 0
        # second write reuses the pooled connection
        client.write_needle(("127.0.0.1", srv.port), 7, 0xDEF, 0x66, b"x")
        assert srv.write_requests == 2
    finally:
        client.close()
        srv.stop()


def test_needle_write_volume_refusal_negative_cachable():
    """A volume-level write refusal (status 2) surfaces with
    volume_refusal=True — clients negative-cache the vid — and the
    pooled connection SURVIVES (the server drains the payload before
    refusing)."""

    def refuse(vid, nid, cookie, data, md):
        raise net_plane.NetPlaneVolumeRefusal("volume not here")

    srv = _write_plane(refuse)
    client = net_plane.NetPlaneClient()
    try:
        with pytest.raises(net_plane.NetPlaneError, match="not here") as ei:
            client.write_needle(
                ("127.0.0.1", srv.port), 1, 2, 3, b"zz" * 5000
            )
        assert getattr(ei.value, "volume_refusal", False)
        with pytest.raises(net_plane.NetPlaneError, match="not here"):
            client.write_needle(("127.0.0.1", srv.port), 1, 9, 3, b"y")
        assert srv.write_requests == 2, "refusal killed the connection"
    finally:
        client.close()
        srv.stop()


def test_needle_write_without_resolver_refused():
    """A read-only sidecar (no resolve_write wired) refuses write
    frames in-protocol instead of dropping the connection."""
    srv = _write_plane(resolve_write=None)
    client = net_plane.NetPlaneClient()
    try:
        with pytest.raises(
            net_plane.NetPlaneError, match="not served here"
        ):
            client.write_needle(("127.0.0.1", srv.port), 1, 2, 3, b"data")
        assert srv.write_requests == 0
    finally:
        client.close()
        srv.stop()


def test_needle_write_stored_crc_mismatch_raises():
    """An ACK whose stored CRC disagrees with what the client sent is
    an error, not a silent accept — end-to-end bit certification."""

    def liar(vid, nid, cookie, data, md):
        return len(data), crc32c(data) ^ 0xBAD

    srv = _write_plane(liar)
    client = net_plane.NetPlaneClient()
    try:
        with pytest.raises(
            net_plane.NetPlaneError, match="stored CRC mismatch"
        ):
            client.write_needle(("127.0.0.1", srv.port), 1, 2, 3, b"abc")
    finally:
        client.close()
        srv.stop()


def test_write_plane_admissible_namespaces():
    """Write-path chaos (ec.net.write.*, volume.write.*) leaves the
    write plane admissible — the crash matrix rides the native path —
    while any OTHER armed point routes writes to the fallback."""
    assert net_plane.write_plane_admissible()
    with faults.injected(
        "ec.net.write.before_pwrite", faults.latency(0.0),
        when=faults.always(),
    ):
        assert net_plane.write_plane_admissible()
    with faults.injected(
        "volume.write.before_fsync", faults.latency(0.0),
        when=faults.always(),
    ):
        assert net_plane.write_plane_admissible()
    with faults.injected(
        "storage.disk.read_at", faults.latency(0.0), when=faults.always()
    ):
        assert not net_plane.write_plane_admissible()


def test_needle_write_refused_when_foreign_chaos_armed():
    """Server-side: an armed non-write fault registry refuses write
    frames (drained, in-protocol) so chaos runs against the gRPC/HTTP
    fallback; write-namespace chaos is served."""
    stored = {}

    def resolve_write(vid, nid, cookie, data, md):
        stored[nid] = data
        return len(data), crc32c(data)

    srv = _write_plane(resolve_write)
    client = net_plane.NetPlaneClient()
    try:
        with faults.injected(
            "unrelated.point", faults.latency(0.0), when=faults.always()
        ):
            with pytest.raises(
                net_plane.NetPlaneError, match="registry armed"
            ):
                client.write_needle(
                    ("127.0.0.1", srv.port), 1, 2, 3, b"k" * 100
                )
        with faults.injected(
            "ec.net.write.before_pwrite", faults.latency(0.0),
            when=faults.always(),
        ):
            client.write_needle(("127.0.0.1", srv.port), 1, 2, 3, b"served")
        assert stored[2] == b"served"
    finally:
        client.close()
        srv.stop()


@pytest.mark.parametrize("plane", ["native", "python"])
def test_blob_write_roundtrip_and_unlink(tmp_path, monkeypatch, plane):
    """kind=blob: extents land at their file offset (sn_recv_file on
    the native plane — socket to disk, CRC fused, zero Python byte
    handling), the ACK CRC matches the payload, and op=unlink removes
    the blob via the resolver."""
    if plane == "python":
        monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
    root = tmp_path / "blobs"

    def resolve_blob(path, op, md):
        p = root / path
        if op == "unlink":
            try:
                os.unlink(p)
            except FileNotFoundError:
                pass
            return None
        p.parent.mkdir(parents=True, exist_ok=True)
        return os.open(p, os.O_CREAT | os.O_RDWR, 0o644)

    srv = _write_plane(resolve_blob=resolve_blob)
    client = net_plane.NetPlaneClient()
    addr = ("127.0.0.1", srv.port)
    data = np.random.default_rng(5).integers(
        0, 256, 123_457, dtype=np.uint8
    ).tobytes()
    try:
        assert client.write_blob(addr, "sub/s.ec00", 8, data) == len(data)
        raw = (root / "sub/s.ec00").read_bytes()
        assert raw[:8] == b"\0" * 8 and raw[8:] == data
        # append-extend the same blob at the watermark
        client.write_blob(addr, "sub/s.ec00", 8 + len(data), b"tail")
        assert (root / "sub/s.ec00").read_bytes()[8 + len(data):] == b"tail"
        if plane == "native":
            assert srv.write_native_bytes == len(data) + 4
        else:
            assert srv.write_python_bytes == len(data) + 4
        client.unlink_blob(addr, "sub/s.ec00")
        assert not (root / "sub/s.ec00").exists()
    finally:
        client.close()
        srv.stop()


# ------------------------------- write path end to end (cluster level)
# Bit identity across transports, sidecar-death fallback, and replica
# fan-out riding the plane — against real master + volume servers.


@pytest.fixture
def write_cluster(tmp_path):
    import time as _time

    from conftest import allocate_port as free_port
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vols = []
    for i in range(2):
        vs = VolumeServer(
            directories=[str(tmp_path / f"v{i}")],
            master=f"localhost:{mport}",
            ip="localhost",
            port=free_port(),
            ec_backend="cpu",
        )
        vs.start()
        vols.append(vs)
    deadline = _time.time() + 10
    while len(master.topo.nodes) < 2:
        assert _time.time() < deadline, "volume servers did not register"
        _time.sleep(0.05)
    yield master, vols
    for vs in vols:
        vs.stop()
    master.stop()


def _canon_record(raw: bytes) -> bytes:
    """Needle record bytes with the append timestamp normalized — the
    only field two transports may legitimately disagree on."""
    from seaweedfs_tpu.storage.needle import Needle

    n = Needle.from_bytes(bytes(raw))
    n.append_at_ns = 1
    return n.to_bytes()


def _latest_record(vs, vid: int, nid: int) -> bytes:
    from seaweedfs_tpu.storage.types import actual_offset

    vol = vs.store.find_volume(vid)
    assert vol is not None
    nv = vol.needle_map.get(nid)
    assert nv is not None
    return vol._pread_record(actual_offset(nv.offset), nv.size)


def _holder(vols, vid):
    for vs in vols:
        if vs.store.find_volume(vid) is not None:
            return vs
    raise AssertionError(f"volume {vid} on no server")


def test_write_bit_identity_plane_vs_http_vs_grpc(write_cluster):
    """ISSUE 18 satellite: the SAME fid written over the native write
    opcode, the HTTP multipart POST, and the gRPC WriteNeedle lands
    byte-identical needle records on disk (timestamp normalized) —
    ragged payload so the fused CRC's tail path is in the loop."""
    import requests as _requests

    from seaweedfs_tpu.client.operations import Operations
    from seaweedfs_tpu.pb import cluster_pb2 as pb
    from seaweedfs_tpu.storage.file_id import FileId

    master, vols = write_cluster
    ops = Operations(f"localhost:{master.port}")
    payload = np.random.default_rng(11).integers(
        0, 256, 123_457, dtype=np.uint8
    ).tobytes()
    try:
        before = sum(v.net_plane.write_requests for v in vols)
        fid = ops.upload(payload, name="same.bin", mime="application/x-test")
        assert sum(v.net_plane.write_requests for v in vols) == before + 1, (
            "upload did not ride the native write plane"
        )
        f = FileId.parse(fid)
        vs = _holder(vols, f.volume_id)
        raw_plane = _latest_record(vs, f.volume_id, f.needle_id)

        # HTTP multipart to the same fid (the bit-identical fallback)
        loc = ops.master.lookup(f.volume_id)[0]
        r = _requests.post(
            f"http://{loc.url}/{fid}",
            files={"file": ("same.bin", payload, "application/x-test")},
        )
        assert r.status_code == 201, r.text
        raw_http = _latest_record(vs, f.volume_id, f.needle_id)

        # in-process gRPC servicer call
        resp = vs.service.WriteNeedle(
            pb.WriteNeedleRequest(
                volume_id=f.volume_id, needle_id=f.needle_id,
                cookie=f.cookie, data=payload, name="same.bin",
                mime="application/x-test", is_replicate=True,
            ),
            None,
        )
        assert not resp.error
        raw_grpc = _latest_record(vs, f.volume_id, f.needle_id)

        assert _canon_record(raw_plane) == _canon_record(raw_http)
        assert _canon_record(raw_http) == _canon_record(raw_grpc)
        assert len(raw_plane) == len(raw_http) == len(raw_grpc)
        assert ops.read(fid) == payload
    finally:
        ops.close()


def test_write_dead_sidecar_falls_back_to_http(write_cluster):
    """Sidecar down (crashed, old binary): the PUT rides HTTP with the
    plane probe memoized — uploads keep succeeding, bytes unchanged."""
    from seaweedfs_tpu.client.operations import Operations

    master, vols = write_cluster
    for vs in vols:
        vs.net_plane.stop()
    ops = Operations(f"localhost:{master.port}")
    try:
        data = b"no-sidecar-today" * 500
        fid = ops.upload(data, name="f.bin")
        assert ops.read(fid) == data
        assert all(v.net_plane.write_requests == 0 for v in vols)
        # second upload: memoized no-plane peer, still fine
        fid2 = ops.upload(data)
        assert ops.read(fid2) == data
    finally:
        ops.close()


def test_write_chaos_routes_to_http_unless_write_namespace(write_cluster):
    """Armed non-write chaos routes PUTs to the HTTP path (where the
    storage fault points live); armed write-path chaos stays on the
    plane so the crash matrix exercises the native path."""
    from seaweedfs_tpu.client.operations import Operations

    master, vols = write_cluster
    ops = Operations(f"localhost:{master.port}")
    data = b"routed-write" * 300
    try:
        with faults.injected(
            "storage.disk.read_at", faults.latency(0.0),
            when=faults.always(),
        ):
            fid = ops.upload(data)
        assert sum(v.net_plane.write_requests for v in vols) == 0
        assert ops.read(fid) == data
        with faults.injected(
            "ec.net.write.before_pwrite", faults.latency(0.0),
            when=faults.always(),
        ):
            fid2 = ops.upload(data)
        assert sum(v.net_plane.write_requests for v in vols) == 1
        assert ops.read(fid2) == data
    finally:
        ops.close()


def test_replica_fanout_rides_plane_bit_identical(write_cluster):
    """replication=001: the primary fans out to its replica over the
    native plane (pooled connection, replicate=False leg) and both
    copies are byte-identical on disk."""
    import requests as _requests

    from seaweedfs_tpu.client.operations import Operations
    from seaweedfs_tpu.storage.file_id import FileId

    master, vols = write_cluster
    ops = Operations(f"localhost:{master.port}")
    payload = np.random.default_rng(13).integers(
        0, 256, 90_001, dtype=np.uint8
    ).tobytes()
    try:
        fid = ops.upload(payload, name="rep.bin", replication="001")
        f = FileId.parse(fid)
        locs = ops.master.lookup(f.volume_id)
        assert len(locs) == 2, "001 => 2 copies"
        # client->primary leg + primary->replica leg, both on the plane
        assert sum(v.net_plane.write_requests for v in vols) == 2
        raws = [
            _latest_record(vs, f.volume_id, f.needle_id) for vs in vols
        ]
        assert _canon_record(raws[0]) == _canon_record(raws[1])
        for loc in locs:
            r = _requests.get(f"http://{loc.url}/{fid}")
            assert r.status_code == 200 and r.content == payload
    finally:
        ops.close()
