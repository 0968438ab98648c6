"""The reader's side of shard plane reads in one native call:
`NetPlaneClient.fetch_many_into` sends every range's request on a
connection of its own and `sn_plane_fetch` lands the answers, with the
interpreter released from the first request to the return. Against real
`ShardNetPlane`s on loopback (and, for answers no plane gives, a holder
made by hand): the bytes and their granule CRCs, the return once `need`
ranges have landed, refusals that leave their connection in frame, torn
and silent holders, destinations handed out in the order the answers'
headers arrive, and one range equal to what `read_into` always landed.
"""

from __future__ import annotations

import os
import socket
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import net_plane
from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as CTX
from seaweedfs_tpu.utils import native, trace

GEN = 11
VID = 3
SIZE = 5 * 4096 + 123  # ragged: a partial granule at the tail
GRANULE = 4096


class Holder:
    """A shard plane over shard files. `slow[sid]`: seconds its resolver
    sleeps before it answers for that shard (the plane's Python code
    then serves every range: nothing is entered in its native table)."""

    def __init__(self, directory, seed):
        rng = np.random.default_rng(seed)
        self.blobs, self.slow = {}, {}
        self._fds: dict[int, int] = {}
        self._lock = threading.Lock()
        for sid in range(CTX.total):
            self.blobs[sid] = rng.integers(0, 256, (1 << 20) + 8192, dtype=np.uint8)
            with open(os.path.join(directory, f"{VID}{CTX.to_ext(sid)}"), "wb") as f:
                f.write(self.blobs[sid].tobytes())
        self.directory = directory
        self.plane = net_plane.ShardNetPlane("127.0.0.1", 0, self.resolve, server_label="holder")
        self.plane.start()
        self.addr = ("127.0.0.1", self.plane.port)

    def resolve(self, vid, sid, gen):
        time.sleep(self.slow.get(sid, 0.0))
        if gen != GEN:
            raise net_plane.NetPlaneError("stale generation")
        with self._lock:
            fd = self._fds.get(sid)
            if fd is None:
                p = os.path.join(self.directory, f"{vid}{CTX.to_ext(sid)}")
                fd = self._fds[sid] = os.open(p, os.O_RDONLY)
        return fd, os.fstat(fd).st_size

    def want(self, sid, off, size=SIZE):
        return self.blobs[sid][off : off + size]

    def close(self):
        """Stop the plane, and wait for its connection threads (one may
        sleep in `resolve` for a range its reader closed) to end, so that
        none closes an fd under a later test."""
        self.plane.stop()
        mine = f"{net_plane.CONN_THREAD_PREFIX}{self.plane.port}"
        deadline = time.monotonic() + 5
        while any(t.name == mine for t in threading.enumerate()) and time.monotonic() < deadline:
            time.sleep(0.01)
        for fd in self._fds.values():
            os.close(fd)


class HandMade:
    """A holder that reads one request a connection and then does what
    `answer(conn, size)` does: the answers that no plane gives."""

    def __init__(self, answer):
        self.answer = answer
        self.sock = socket.socket()
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(8)
        self.addr = self.sock.getsockname()
        self.conns: list[socket.socket] = []
        self.thread = threading.Thread(target=self._serve, daemon=True)
        self.thread.start()

    def _serve(self):
        while True:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            self.conns.append(conn)
            head = net_plane._recv_exact(conn, net_plane._REQ.size)
            *_, size, mlen = net_plane._REQ.unpack(head)
            net_plane._recv_exact(conn, mlen)
            self.answer(conn, size)

    def close(self):
        self.sock.shutdown(socket.SHUT_RDWR)
        self.sock.close()
        self.thread.join(5)
        for c in self.conns:
            c.close()


@pytest.fixture
def rig(tmp_path):
    made = []

    def holder(seed=1):
        d = tmp_path / f"h{len(made)}"
        d.mkdir()
        made.append(Holder(str(d), seed))
        return made[-1]

    client = net_plane.NetPlaneClient(timeout=2.0, connect_timeout=1.0)
    yield holder, client
    client.close()
    for h in made:
        h.close()


def pooled(client, addr) -> int:
    return len(client._npool.get(addr, []))


def dsts(n, size=SIZE):
    return list(np.zeros((n, size), np.uint8))


def test_n_ranges_land_byte_exact_with_the_granule_crcs_of_their_bytes(rig):
    holder, client = rig
    a, b = holder(1), holder(2)
    ranges = [(a.addr, VID, 0, GEN, 0, SIZE), (b.addr, VID, 5, GEN, 4096, SIZE),
              (a.addr, VID, 9, GEN, 777, SIZE), (b.addr, VID, 13, GEN, 2 * SIZE, SIZE)]
    out = dsts(len(ranges))
    got = client.fetch_many_into(ranges, out, granule=GRANULE)
    assert [o.status for o in got] == ["landed"] * 4
    assert sorted(o.dst for o in got) == [0, 1, 2, 3]
    for (addr, _v, sid, _g, off, size), o in zip(ranges, got):
        h = a if addr == a.addr else b
        assert np.array_equal(out[o.dst], h.want(sid, off))
        want = native.crc32c_granules(out[o.dst][None, :], GRANULE)[0]
        assert np.array_equal(o.crcs, want) and len(o.crcs) == -(-size // GRANULE)
        assert o.t_checkout <= o.t_connected <= o.t_sent <= o.t_header <= o.t_landed
        assert o.payload_began and o.n == o.got == size
    # every connection read to its end goes back to its pool
    assert pooled(client, a.addr) == pooled(client, b.addr) == 2


def test_the_call_returns_after_need_and_what_is_in_flight_is_closed_not_pooled(rig):
    holder, client = rig
    h = holder()
    h.slow[4] = 1.0
    ranges = [(h.addr, VID, sid, GEN, 0, SIZE) for sid in (4, 6, 8)]
    t0 = time.monotonic()
    got = client.fetch_many_into(ranges, dsts(3), granule=GRANULE, need=2)
    assert time.monotonic() - t0 < 0.8  # not the second the slow shard takes
    assert [o.status for o in got] == ["in_flight", "landed", "landed"]
    slow = got[0]
    assert slow.t_sent and not slow.t_landed and slow.dst is None and slow.crcs is None
    # the two landed connections are pooled; the one in flight is closed
    assert pooled(client, h.addr) == 2
    # and the pooled ones are in frame: the next call takes them
    again = client.fetch_many_into(ranges[1:], dsts(2), granule=GRANULE)
    assert [o.status for o in again] == ["landed", "landed"]


def test_a_stale_generation_is_refused_and_its_connection_pooled_in_frame(rig):
    holder, client = rig
    h = holder()
    out = dsts(2)
    got = client.fetch_many_into(
        [(h.addr, VID, 2, GEN + 1, 0, SIZE), (h.addr, VID, 3, GEN, 0, SIZE)], out, granule=GRANULE
    )
    assert [o.status for o in got] == ["refused", "landed"]
    assert "stale generation" in got[0].message and not got[0].payload_began
    assert got[1].dst == 0 and np.array_equal(out[0], h.want(3, 0))
    assert not out[1].any()  # nothing landed for the refusal
    assert pooled(client, h.addr) == 2
    with pytest.raises(net_plane.NetPlaneError, match="stale generation"):
        client.read_into(h.addr, VID, 2, GEN + 1, 0, SIZE, out[1])
    # both pooled connections answer the next requests in frame
    got = client.fetch_many_into([(h.addr, VID, 2, GEN, 0, SIZE)] * 2, dsts(2))
    assert [o.status for o in got] == ["landed", "landed"]


def test_a_holder_that_closes_mid_payload_is_torn_and_not_pooled():
    def half_then_close(conn, size):
        conn.sendall(net_plane._RESP.pack(0, size) + b"x" * (size // 2))
        conn.close()

    hand = HandMade(half_then_close)
    client = net_plane.NetPlaneClient(timeout=2.0, connect_timeout=1.0)
    try:
        out = dsts(1)
        (o,) = client.fetch_many_into([(hand.addr, VID, 1, GEN, 0, SIZE)], out, granule=GRANULE)
        assert o.status == "torn" and o.payload_began and 0 < o.got < SIZE
        assert pooled(client, hand.addr) == 0
        with pytest.raises(net_plane.NetPlaneError, match="torn"):
            client.read_into(hand.addr, VID, 1, GEN, 0, SIZE, out[0])
    finally:
        client.close()
        hand.close()


def test_a_silent_holder_times_out_and_its_connection_is_closed():
    hand = HandMade(lambda conn, size: None)  # reads the request, says nothing
    client = net_plane.NetPlaneClient(timeout=0.3, connect_timeout=1.0)
    try:
        t0 = time.monotonic()
        (o,) = client.fetch_many_into([(hand.addr, VID, 1, GEN, 0, SIZE)], dsts(1))
        assert o.status == "timed_out" and 0.25 < time.monotonic() - t0 < 2.0
        assert o.t_sent and not o.t_header and pooled(client, hand.addr) == 0
    finally:
        client.close()
        hand.close()


def test_destinations_are_handed_out_in_the_order_the_headers_arrive(rig):
    holder, client = rig
    h = holder()
    h.slow[0] = 0.3  # the first range asked answers last
    ranges = [(h.addr, VID, 0, GEN, 0, SIZE), (h.addr, VID, 1, GEN, 0, SIZE),
              (h.addr, VID, 2, GEN, 0, SIZE)]
    out = dsts(3)
    got = client.fetch_many_into(ranges, out, granule=GRANULE)
    assert [o.status for o in got] == ["landed"] * 3
    # the answer that came last took the last destination
    assert got[0].t_header > max(got[1].t_landed, got[2].t_landed)
    assert got[0].dst == 2 and {got[1].dst, got[2].dst} == {0, 1}
    for j, o in enumerate(got):
        assert np.array_equal(out[o.dst], h.want(j, 0))


@pytest.mark.parametrize("size", [SIZE, 1 << 20], ids=["ragged", "wide"])
def test_one_range_is_what_read_into_always_landed(rig, monkeypatch, size):
    """`read_into` is the call with one range; what it lands and the CRCs
    it hands back are those of the same range over the Python wire
    (`read_bytes`, and the Python body with no library)."""
    holder, client = rig
    h = holder()
    dst = np.zeros(size, np.uint8)
    crcs = client.read_into(h.addr, VID, 7, GEN, 100, size, dst, granule=GRANULE)
    raw = client.read_bytes(h.addr, VID, 7, GEN, 100, size)
    assert dst.tobytes() == raw == h.blobs[7][100 : 100 + size].tobytes()
    assert np.array_equal(crcs, native.crc32c_granules(dst[None, :], GRANULE)[0])
    assert client.read_into(h.addr, VID, 7, GEN, 100, size, dst) is None  # no granule, no CRCs
    monkeypatch.setattr(net_plane, "_native_mod", lambda: None)
    py = np.zeros(size, np.uint8)
    py_crcs = client.read_into(h.addr, VID, 7, GEN, 100, size, py, granule=GRANULE)
    assert py.tobytes() == raw and np.array_equal(py_crcs, crcs)


def test_without_the_library_the_same_wire_in_python_stops_at_need(rig, monkeypatch):
    holder, client = rig
    h = holder()
    monkeypatch.setattr(net_plane, "_native_mod", lambda: None)
    ranges = [(h.addr, VID, sid, GEN, 0, SIZE) for sid in (1, 2, 3)]
    out = dsts(3)
    got = client.fetch_many_into(ranges, out, granule=GRANULE, need=2)
    assert [o.status for o in got] == ["landed", "landed", "not_asked"]
    for j in (0, 1):
        assert np.array_equal(out[got[j].dst], h.want(j + 1, 0))
    assert pooled(client, h.addr) == 3  # the one not asked is in frame too


def test_armed_the_call_books_one_return_to_the_span_whose_stage_is_open(rig, disarmed):
    holder, client = rig
    h = holder()
    trace.configure(enabled=True)
    sp = trace.Span("ec.degraded_read")
    ranges = [(h.addr, VID, sid, GEN, 0, SIZE) for sid in range(6)]
    made = []

    def span_of(i):
        made.append(sp.child("ec.peer_read", shard=ranges[i][2]))
        return made[-1]

    with trace.stage(sp, "peer_read"):
        got = client.fetch_many_into(ranges, dsts(6), granule=GRANULE, span_of=span_of)
    assert all(o.status == "landed" for o in got) and len(made) == 6
    assert sp.attrs["interp_returns"] == 1 and sp.attrs["interp_wait_ns"] >= 0
    assert all("interp_returns" not in c.attrs for c in made)
