"""Native shard byte plane: the network half of the zero-copy EC path.

PR 10 made the LOCAL byte path native; every network byte still
round-tripped through Python — `VolumeEcShardRead` serializes pooled
buffers into Python gRPC messages, and peer-fetch rebuild re-buffers
fetched ranges through `bytes`. This module is the wire twin of that
RPC (the analog of the reference architecture's native RDMA data-plane
engine, PAPER.md layer map): a tiny TCP sidecar next to each volume
server's gRPC port that serves EC shard byte ranges with

- **native egress**: `sn_send_file` splices the shard fd straight into
  the socket (sendfile(2), kernel-to-kernel, GIL released); since ISSUE
  39 a connection thread serves a range request whole inside ONE native
  call (`sn_plane_serve`: the wait, the header and metadata, the look-up
  in the plane's table of shard fds, the answer's header and the
  sendfile), and Python only books it after the reader has its bytes;
- **native ingress**: the client lands streams DIRECTLY in caller-owned
  pooled 4096-aligned buffers (`sn_recv_into`) with the fused
  granule-CRC32C rolling during the copy-in, so the sidecar verify in
  ec/peer_rebuild.py costs no extra byte pass.

The plane is an ACCELERATOR, not a dependency: gRPC `VolumeEcShardRead`
remains the canonical, generation-fenced transport and the
bit-identical fallback. Fallback routing (the same contract as PR 10's
local plane):

- `SEAWEED_EC_NATIVE=0` or a missing .so: callers never take this path
  (ec/native_io.enabled() is the single gate);
- an ARMED fault registry: the server answers through the Python
  pread/sendall path so byte-mutating chaos has materialized bytes to
  chew on, and peer_rebuild routes its client side to the Python fetch
  — the PR 6/8/11 chaos contracts hold unchanged;
- a peer without the sidecar (older build, port collision): the client
  memoizes the refusal and raises :class:`NetPlaneUnavailable`, which
  peer_rebuild turns into a per-stream fallback to the gRPC fetch.

Protocol (little-endian, persistent connection, one in-flight request
per connection):

    request:  b"SWNP" | u32 volume_id | u32 shard_id | u64 generation
              | u64 offset | u64 size | u16 meta_len       (38 bytes)
              | meta_len bytes of "key\\tvalue" lines — the SAME
              x-sw-trace-id / x-sw-parent-span / x-request-id metadata
              the gRPC stream carries, so a peer-fetch over the native
              plane still lands in the dispatcher's ONE trace (the
              PR 7 cross-RPC contract holds transport-independently)
    response: u8 status | u64 n | n bytes
              status 0 = ok (n = payload length, may be < size at EOF);
              status 1 = error (n = UTF-8 message length);
              status 2 = VOLUME-level refusal (needle opcode: the whole
              volume can never be served here — EC/TTL'd/tiered — so
              clients negative-cache the vid instead of paying a
              refusal round trip per chunk; same frame shape as 1)

The sidecar listens on ``grpc_port + NET_PLANE_PORT_OFFSET`` so peers
derive its address from the holder map's gRPC address without any new
topology plumbing; a dead port is just a memoized fallback.
"""

from __future__ import annotations

import base64
import os
import socket
import struct
import threading
import time

import numpy as np

from .. import faults
from ..utils import metrics as M
from ..utils import request_id as _rid
from ..utils import trace
from ..utils.glog import logger

log = logger("ec.netplane")

MAGIC = b"SWNP"
# Needle/chunk-read opcode (ISSUE 13): the warm gateway path's
# filer->volume chunk fetch over the SAME sidecar and framing. The
# 38-byte header shape is reused with reinterpreted fields —
# shard -> cookie, generation -> needle id, offset/size unused — and
# the OK response carries the needle's stored CRC32C between the
# length and the payload, so the client's fused copy-in CRC verifies
# with no extra byte pass.
MAGIC_NEEDLE = b"SWNR"
# Needle/blob WRITE opcode (ISSUE 18): the same 38-byte header frames a
# PUT — for kind=needle the fields are reinterpreted shard -> cookie,
# generation -> needle id, offset -> the CLIENT-computed CRC32C of the
# payload (so the server's fused copy-in CRC verifies transit with no
# extra byte pass); for kind=blob (remote stream-shard extents) offset
# is the real file offset and the CRC rides the metadata. The payload
# (`size` bytes) follows the metadata. An OK response carries
# n = stored size and the _NEEDLE_CRC trailer = the CRC as STORED, which
# the client compares against what it sent — an ack therefore certifies
# the exact bytes that hit the disk, end to end. Refusals (status 1/2)
# are sent only after the payload is drained, so the persistent
# connection stays in frame sync and pooled connections survive
# refusals.
MAGIC_WRITE = b"SWNW"
# magic, volume, shard, gen, offset, size, meta_len
_REQ = struct.Struct("<4sIIQQQH")
_RESP = struct.Struct("<BQ")      # status, n
_NEEDLE_CRC = struct.Struct("<I")  # appended to an OK needle response
NET_PLANE_PORT_OFFSET = 10000     # net plane port = grpc port + this

# name prefix of a plane's connection threads (the port follows)
CONN_THREAD_PREFIX = "shard-net-conn-"

_SEND_CHUNK = 1 << 20             # python-plane egress chunking
_MAX_REQUEST = 1 << 32
_MAX_META = 4096
# error-response bodies are short refusal strings; a length beyond this
# means the stream desynced (or a hostile peer) — allocating it blindly
# would raise MemoryError past the callers' NetPlaneError fallback
_MAX_ERROR = 1 << 16
# needle payloads beyond this ride the HTTP path: chunks are filer
# chunk_size (MiBs), so a bigger OK-frame length is a desynced/hostile
# response — landing it would pin an immortal pooled buffer that size
_MAX_NEEDLE = 64 << 20
# never park landing buffers wider than this in the process-wide pool
_POOL_MAX_WIDTH = 8 << 20
# blob writes (stream-shard extents pushed at flush boundaries) may be
# wider than a needle; anything beyond this is a desynced/hostile frame
_MAX_BLOB = 256 << 20

# Write-opcode chaos routing: the write plane keeps serving while the
# ONLY armed fault points live on the write path's own seams (the
# net-plane pwrite window and the volume append/fsync window) — that is
# exactly the crash matrix that must ride the native path. Any OTHER
# armed point (byte-mutating storage chaos, read-path faults) refuses
# write service so the Python/gRPC fallback — which carries those
# points — stays the chaos surface, same contract as the read opcodes.
_WRITE_CHAOS_NS = ("ec.net.write.", "volume.write.")


def write_plane_admissible() -> bool:
    """True when the write opcode may serve despite an armed registry:
    every armed point lives in the write path's own chaos namespaces
    (or nothing is armed at all)."""
    return all(
        p.startswith(_WRITE_CHAOS_NS) for p in faults.armed_points()
    )


def _pool_width(n: int) -> int:
    """Pool width class for an n-byte payload. The landing pool
    free-lists by EXACT width and retains forever — pooling raw payload
    sizes (objects/tail chunks take arbitrary sizes) would grow one
    immortal buffer per distinct size. Rounding up to the next power of
    two (floor 64 KiB) bounds the class count to ~a dozen regardless of
    object-size mix."""
    return max(64 * 1024, 1 << (max(1, n) - 1).bit_length())


def _encode_meta(extra: dict | None = None, span=None) -> bytes:
    """The active request-id / trace context (that of `span` where one
    is given) as a metadata blob — exactly what trace.grpc_metadata()
    would put on the RPC — plus any opcode-specific key/value pairs (the
    write opcode's kind / flags / name / jwt lines). Values must not
    contain tab or newline; binary fields ride urlsafe base64 (see
    _b64)."""
    md = list(trace.grpc_metadata(span) or [])
    if extra:
        md.extend(
            (k, str(v)) for k, v in extra.items()
            if v is not None and str(v) != ""
        )
    if not md:
        return b""
    blob = "\n".join(f"{k}\t{v}" for k, v in md).encode()
    return blob[:_MAX_META]


def _b64(value: bytes | str) -> str:
    if isinstance(value, str):
        value = value.encode()
    return base64.urlsafe_b64encode(value).decode()


def _unb64(value: str) -> bytes:
    try:
        return base64.urlsafe_b64decode(value.encode())
    except (ValueError, TypeError):
        return b""


def _decode_meta(blob: bytes) -> dict:
    md: dict = {}
    for line in blob.decode(errors="replace").splitlines():
        k, _, v = line.partition("\t")
        if k and v:
            md[k.lower()] = v
    return md


class NetPlaneError(Exception):
    """Transport/protocol failure on an established plane connection —
    transient from the caller's point of view (retry or fall back)."""


class NetPlaneVolumeRefusal(NetPlaneError):
    """Needle-opcode refusal that applies to the WHOLE volume (not
    mounted here / EC / TTL'd / tiered): the server answers status 2 so
    clients can negative-cache the vid. Raised by resolve_needle
    implementations server-side; surfaces client-side as a
    NetPlaneError with ``volume_refusal=True``."""


class NetPlaneUnavailable(Exception):
    """The peer serves no shard net plane (connect refused / bad
    protocol greeting). Memoized per peer; callers route the stream to
    the gRPC fetch instead."""


def derive_port(grpc_port: int) -> int:
    """Net-plane port derived from a gRPC port — the SAME pure function
    on the serving and connecting side, so no topology plumbing is
    needed. High ephemeral gRPC ports wrap back into the valid range
    deterministically; a collision there just fails the bind (server:
    plane disabled with one warning) or the connect (client: memoized
    gRPC fallback)."""
    p = grpc_port + NET_PLANE_PORT_OFFSET
    if p > 65535:
        p = 1024 + (p % 64512)
    return p


def net_addr(grpc_peer: str) -> tuple[str, int]:
    """Net-plane (host, port) derived from a holder-map gRPC address."""
    host, _, port = grpc_peer.rpartition(":")
    return host, derive_port(int(port))


def _native_mod():
    try:
        from ..utils import native

        return native
    except ImportError:
        return None


def egress_native() -> bool:
    """True when the server side should splice with sendfile: native
    plane on AND the fault registry disarmed (byte-mutating chaos needs
    materialized bytes — the armed registry routes to the Python
    egress, same contract as the local plane)."""
    from . import native_io

    return native_io.enabled() and not faults.active()


_watching_faults = False


def _watch_faults(native) -> None:
    """Once a process: the native tables serve only while the fault
    registry is disarmed, pushed to the C side on every arm and disarm
    (a connection thread waits for its next request without the
    interpreter, so it cannot ask `faults.active()` itself)."""
    global _watching_faults
    if not _watching_faults:
        _watching_faults = True
        faults.REGISTRY.watch(lambda armed: native.plane_set_admissible(not armed))


def _close(sock: socket.socket) -> None:
    try:
        sock.close()
    except OSError:
        pass


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise NetPlaneError("connection closed mid-message")
        got += r
    return bytes(buf)


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------


class ShardNetPlane:
    """TCP sidecar serving EC shard byte ranges — the native twin of the
    ``VolumeEcShardRead`` gRPC stream, sharing its semantics (generation
    fence, short-read-at-EOF torn-stream contract, the
    ``server.ec_shard_read`` chaos point) but not its byte path.

    ``resolve(volume_id, shard_id, generation) -> (fd, size)`` supplies
    the shard fd and its byte size; it raises :class:`NetPlaneError`
    with the refusal message (not mounted / stale generation / shard
    not local). The server never closes resolved fds — they belong to
    the store's mounted EC volume, exactly like the gRPC servicer.

    ``resolve_needle(volume_id, needle_id, cookie) -> (fd, offset,
    size, crc32c, close_after)`` (optional) supplies a needle payload's
    location for the chunk-read opcode — the net-plane twin of the
    ``?locate=true`` control plane; ``close_after`` marks fds the
    server must close once the response is sent (per-request opens).
    Raising :class:`NetPlaneError` refuses the request (not here / EC /
    TTL'd / cookie mismatch) and the client falls back to HTTP.

    ``resolve_write(volume_id, needle_id, cookie, data, md) ->
    (stored_size, stored_crc)`` (optional) lands one needle append for
    the write opcode — the net-plane twin of the ``WriteNeedle`` gRPC —
    building the SAME needle record the gRPC/HTTP paths build (bit
    identity on disk) and triggering replica fan-out unless the request
    is itself a replica. :class:`NetPlaneVolumeRefusal` means the whole
    volume can never take plane writes here; :class:`NetPlaneError` /
    ``IOError`` / ``ValueError`` refuse this one write (client retries
    over the fallback transport).

    The native table (with the .so): :meth:`admit` enters a shard the
    resolver has vouched for, and the connection threads then serve its
    ranges inside one native call each. ``resolve``'s owner calls it
    under the lock that keeps the fd open, and an entry leaves the table
    through ec/ec_volume.py's shard-fd hook, which :meth:`start`
    subscribes to, before the volume closes or replaces that fd.
    Whatever the table does not serve (the other opcodes, a miss, a
    stale generation, an armed fault registry) comes back to the Python
    code below with its header and metadata read.

    ``resolve_blob(path, op, md) -> fd | None`` (optional) serves
    kind=blob writes — remote durable-parity stream-shard extents. It
    validates `path` against the server's blob root, returning an fd
    the server pwrites into and closes (``op == "write"``), or handling
    the operation itself and returning None (``op == "unlink"``).
    """

    def __init__(self, ip: str, port: int, resolve,
                 request_timeout: float = 60.0, server_label: str = "",
                 resolve_needle=None, resolve_write=None,
                 resolve_blob=None):
        self.resolve = resolve
        self.resolve_needle = resolve_needle
        self.resolve_write = resolve_write
        self.resolve_blob = resolve_blob
        self.request_timeout = request_timeout
        self.server_label = server_label
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((ip, port))
        self._sock.listen(128)
        self.ip, self.port = self._sock.getsockname()[:2]
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._accept_loop, daemon=True, name="shard-net-plane"
        )
        self._conns: set[socket.socket] = set()
        self._conns_lock = threading.Lock()
        self.requests = 0
        self.needle_requests = 0
        # what left through either egress, summed under a lock: many
        # connection threads add to them, and the readers' counters are
        # held against them to the byte
        self._sent_lock = threading.Lock()
        self.sendfile_bytes = 0
        self.python_bytes = 0
        self.write_requests = 0
        self.write_native_bytes = 0
        self.write_python_bytes = 0
        # range requests served inside one native call
        self.native_serves = 0
        self._native = _native_mod()
        self._table = self._native.PlaneTable() if self._native else None
        # the (volume, shard)s in the table, and the lock of its changes
        self._admitted: set[tuple[int, int]] = set()
        self._admit_lock = threading.Lock()
        if self._native is not None:
            _watch_faults(self._native)

    def start(self) -> None:
        if self._table is not None:
            from . import ec_volume

            ec_volume.add_shard_fd_hook(self._shard_fds_change)
        self._thread.start()

    def admit(self, vid: int, sid: int, generation: int, fd: int) -> None:
        """Let the native call serve shard `sid` of volume `vid` at
        `generation` from a duplicate of `fd`. The caller holds what
        keeps `fd` open and sees to it that the shard-fd hook runs
        before `fd` is closed or replaced. Nothing without the .so."""
        if self._table is None:
            return
        with self._admit_lock:
            if self._stop.is_set() or (vid, sid) in self._admitted:
                return
            self._table.put(vid, sid, generation, fd)
            self._admitted.add((vid, sid))

    def _shard_fds_change(self, vid: int, sids) -> None:
        """ec/ec_volume.py's shard-fd hook: the entries go first. Any
        volume's change drops them: a later request re-admits."""
        with self._admit_lock:
            for sid in sids:
                if (vid, sid) in self._admitted:
                    self._admitted.discard((vid, sid))
                    self._table.drop(vid, sid)

    def stop(self) -> None:
        self._stop.set()
        if self._table is not None:
            from . import ec_volume

            ec_volume.remove_shard_fd_hook(self._shard_fds_change)
            with self._admit_lock:
                self._table.clear()
                self._admitted.clear()
        try:
            # close() alone does not wake a thread blocked in accept();
            # shutdown() does, so the join below returns immediately
            # instead of eating its full timeout
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        with self._conns_lock:
            conns = list(self._conns)
        for c in conns:
            # as with the listener: close() alone neither wakes the
            # connection's thread out of its recv nor tells the peer, so
            # a reader that has the connection parked in its pool would
            # send its next request into a socket that nobody serves and
            # wait out its whole time-out
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            _close(c)
        self._thread.join(timeout=2.0)

    # ------------------------------------------------------------ serving

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return  # listener closed
            try:
                # the answer's header and a range shorter than a segment
                # leave at once, not held for the ACK of what went before
                # (Nagle's wait: 40 ms a short range where ACKs are delayed)
                conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                pass  # the peer is gone already: its first read says so
            with self._conns_lock:
                self._conns.add(conn)
            # named: the wait probes sum CPU by class of thread from the
            # name (utils/interp_probe.py)
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True,
                name=f"{CONN_THREAD_PREFIX}{self.port}",
            ).start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            conn.settimeout(self.request_timeout)
            if self._table is not None:
                # where the native call leaves the request and its stamps
                rec = np.zeros(len(self._native.PLANE_REC), np.uint64)
                meta = np.zeros(_MAX_META, np.uint8)
            while not self._stop.is_set():
                if self._table is not None:
                    req = self._next_native(conn, rec, meta)
                else:
                    req = self._next_python(conn)
                if req is None or not self._dispatch(conn, *req):
                    return
        finally:
            with self._conns_lock:
                self._conns.discard(conn)
            _close(conn)

    def _next_python(self, conn):
        """The next request's (magic, vid, sid, gen, off, size, md), read
        by Python; None: the connection is to close."""
        try:
            hdr = _recv_exact(conn, _REQ.size)
        except (NetPlaneError, OSError):
            return None  # client went away between requests
        magic, vid, sid, gen, off, size, mlen = _REQ.unpack(hdr)
        if (
            magic not in (MAGIC, MAGIC_NEEDLE, MAGIC_WRITE)
            or size > _MAX_REQUEST
            or mlen > _MAX_META
        ):
            return None  # not our protocol: drop the connection
        try:
            md = _decode_meta(_recv_exact(conn, mlen)) if mlen else {}
        except (NetPlaneError, OSError):
            return None
        return magic, vid, sid, gen, off, size, md

    def _next_native(self, conn, rec, meta):
        """Serve range requests inside the native call for as long as it
        can; -> the first request it hands back, as `_next_python` gives
        it, or None: the connection is to close (idle, closed by the
        peer, not our protocol, or a served range that left it out of
        frame)."""
        from . import native_io

        native = self._native
        timeout_ms = int(self.request_timeout * 1000)
        while True:
            what = self._table.serve(
                conn.fileno(), timeout_ms, native_io.enabled(), rec, meta
            )
            if what == native.PLANE_SERVED:
                if not self._book_native(rec, meta) or self._stop.is_set():
                    return None
                continue
            if what != native.PLANE_HANDED_BACK:
                return None
            _w, magic, vid, sid, gen, off, size, mlen = rec[:8].tolist()
            md = _decode_meta(meta[:mlen].tobytes()) if mlen else {}
            return magic.to_bytes(4, "little"), vid, sid, gen, off, size, md

    def _book_native(self, rec, meta) -> bool:
        """A range the native call served: count it and, armed, leave its
        `rpc.ec_shard_read` span, laid on the call's stamps. -> whether
        the connection is still in frame."""
        (_w, _m, vid, sid, _g, off, size, mlen, n, sent, ok,
         t_req, t_res, t_hdr, t_sent, c_req, c_res, c_hdr, c_sent) = rec.tolist()
        self.requests += 1
        with self._sent_lock:
            self.native_serves += 1
        M.ec_plane_serves_total.inc(path="native")
        if sent:
            self._sent("native", sent)
        if not trace.armed:
            return bool(ok)
        md = _decode_meta(meta[:mlen].tobytes()) if mlen else {}
        _rid.ensure(md.get(trace.REQUEST_ID_KEY))
        sp = trace.start_from_metadata(
            "rpc.ec_shard_read", md, server=self.server_label,
            volume=vid, shard=sid, offset=off, size=size,
            plane="native", served="native",
        )
        if sp is not None:
            sp.backdate(t_req, c_req)
            sp.add_interval("stream", t_req, t_sent, c_sent - c_req)
            sp.add_interval("stream.resolve", t_req, t_res, c_res - c_req)
            sp.add_interval("stream.header", t_res, t_hdr, c_hdr - c_res)
            if n:
                sp.add_interval("stream.sendfile", t_hdr, t_sent, c_sent - c_hdr)
            sp.finish(t_sent)
        return bool(ok)

    def _dispatch(self, conn, magic, vid, sid, gen, off, size, md) -> bool:
        """Serve one request Python has in hand; False = connection must
        close."""
        self.requests += 1
        # Observability parity with the gRPC stream: adopt the
        # caller's request id + trace context and open the SAME
        # rpc.ec_shard_read span — a peer-fetch heal stays ONE
        # trace whichever transport carried the bytes. Needle
        # reads open rpc.needle_read instead, joined to the
        # gateway's trace the same way — one warm GET stays
        # ONE trace across the chunk-fetch hop.
        _rid.ensure(md.get(trace.REQUEST_ID_KEY))
        if magic == MAGIC_WRITE:
            # field reinterpretation (kind=needle): sid slot =
            # cookie, gen slot = needle id, off slot = the
            # client's payload CRC32C
            sp = trace.start_from_metadata(
                "rpc.needle_write", md, server=self.server_label,
                volume=vid, needle=gen, size=size, plane="native",
            )
            t0 = time.perf_counter()
            try:
                return self._serve_write(conn, vid, sid, gen, off, size, md)
            finally:
                trace.add_stage(sp, "stream", time.perf_counter() - t0)
                trace.finish(sp)
        if magic == MAGIC_NEEDLE:
            # field reinterpretation: sid slot = cookie,
            # gen slot = needle id
            sp = trace.start_from_metadata(
                "rpc.needle_read", md, server=self.server_label,
                volume=vid, needle=gen, plane="native",
            )
            t0 = time.perf_counter()
            try:
                return self._serve_needle(conn, vid, gen, sid)
            finally:
                trace.add_stage(sp, "stream", time.perf_counter() - t0)
                trace.finish(sp)
        M.ec_plane_serves_total.inc(path="python")
        # its parent is the reader's `ec.peer_read` span, one to
        # one; `stream` is open on this thread while the range
        # is served, so _serve_one laps its parts and the
        # stamped return of `send_file` is booked here
        sp = trace.start_from_metadata(
            "rpc.ec_shard_read", md, server=self.server_label,
            volume=vid, shard=sid, offset=off, size=size,
            plane="native", served="python",
        )
        try:
            with trace.stage(sp, "stream"):
                return self._serve_one(conn, vid, sid, gen, off, size)
        finally:
            trace.finish(sp)

    def _sent(self, egress: str, n: int) -> None:
        """`n` bytes left through `egress` (native | python)."""
        with self._sent_lock:
            if egress == "native":
                self.sendfile_bytes += n
            else:
                self.python_bytes += n
        M.net_bytes_sent_total.inc(n, plane=egress, direction="read")

    def _error(self, conn, msg: str, status: int = 1) -> bool:
        body = msg.encode(errors="replace")
        try:
            conn.sendall(_RESP.pack(status, len(body)) + body)
            return True
        except OSError:
            return False

    def _serve_one(self, conn, vid, sid, gen, off, size) -> bool:
        """Serve one range request; False = connection must close.
        Armed, the caller's `stream` stage is split into `.resolve`,
        `.header` and `.sendfile` (one module-bool check each where
        not)."""
        trace.lap("resolve")
        try:
            # Same named chaos point as the gRPC servicer: a raised
            # IOError is a refused stream (client replans); a mutate is
            # applied on the PYTHON egress below — the armed registry
            # routes there, never through sendfile.
            faults.fire("server.ec_shard_read", volume=vid, shard=sid)
        except IOError as e:
            return self._error(conn, str(e))
        try:
            fd, fsize = self.resolve(vid, sid, gen)
        except NetPlaneError as e:
            return self._error(conn, str(e))
        n = max(0, min(size, fsize - off)) if off < fsize else 0
        trace.lap("header")
        try:
            conn.sendall(_RESP.pack(0, n))
        except OSError:
            return False
        if n == 0:
            return True
        trace.lap("sendfile")
        native = _native_mod() if egress_native() else None
        if native is not None:
            try:
                sent = native.send_file(
                    conn.fileno(), fd, off, n,
                    timeout_ms=int(self.request_timeout * 1000),
                )
            except OSError:
                return False  # peer died mid-splice: header already out
            self._sent("native", sent)
            return sent == n
        # Python egress (fallback plane / armed registry): pread ->
        # mutate -> sendall, byte-identical to the gRPC stream's
        # chunking. A mutate that shrinks the chunk tears the stream,
        # which the client must catch — never served silently.
        remaining, o = n, off
        while remaining > 0:
            chunk = os.pread(fd, min(_SEND_CHUNK, remaining), o)
            if not chunk:
                break
            orig = len(chunk)
            chunk = faults.mutate(
                "server.ec_shard_read", chunk, volume=vid, shard=sid, offset=o
            )
            M.net_bytes_copied_total.inc(orig, plane="python", direction="read")
            try:
                if chunk:
                    conn.sendall(chunk)
            except OSError:
                return False
            self._sent("python", len(chunk))
            if len(chunk) < orig:
                return False  # torn stream: connection is dead
            o += orig
            remaining -= orig
        return remaining == 0

    def _serve_needle(self, conn, vid, nid, cookie) -> bool:
        """Serve one whole-needle payload (the warm gateway chunk
        fetch); False = connection must close. Refused outright when
        the fault registry is ARMED: byte-mutating chaos belongs to the
        Python-HTTP path, which carries the storage-layer fault points
        — the client's fallback is the chaos surface, same contract as
        the peer-fetch plane."""
        if self.resolve_needle is None:
            return self._error(conn, "needle reads not served here")
        if faults.active():
            return self._error(conn, "fault registry armed: use HTTP")
        try:
            fd, off, size, crc, close_after = self.resolve_needle(
                vid, nid, cookie
            )
        except NetPlaneVolumeRefusal as e:
            # the whole volume can never be served here: status 2 lets
            # the client negative-cache the vid
            return self._error(conn, str(e), status=2)
        except NetPlaneError as e:
            return self._error(conn, str(e))
        self.needle_requests += 1
        try:
            try:
                conn.sendall(
                    _RESP.pack(0, size) + _NEEDLE_CRC.pack(crc & 0xFFFFFFFF)
                )
            except OSError:
                return False
            if size == 0:
                return True
            native = _native_mod() if egress_native() else None
            if native is not None:
                try:
                    sent = native.send_file(
                        conn.fileno(), fd, off, size,
                        timeout_ms=int(self.request_timeout * 1000),
                    )
                except OSError:
                    return False
                self._sent("native", sent)
                return sent == size
            # Python egress (no .so): pread -> sendall, the same bytes.
            remaining, o = size, off
            while remaining > 0:
                chunk = os.pread(fd, min(_SEND_CHUNK, remaining), o)
                if not chunk:
                    return False  # short file: torn stream
                M.net_bytes_copied_total.inc(len(chunk), plane="python", direction="read")
                try:
                    conn.sendall(chunk)
                except OSError:
                    return False
                self._sent("python", len(chunk))
                o += len(chunk)
                remaining -= len(chunk)
            return True
        finally:
            if close_after:
                try:
                    os.close(fd)
                except OSError:
                    pass

    # ------------------------------------------------------------- writes

    @staticmethod
    def _drain(conn, n: int) -> bool:
        """Consume `n` unread payload bytes so a refusal sent AFTER the
        header leaves the persistent connection in frame sync — pooled
        client connections survive refusals instead of desyncing."""
        if n <= 0:
            return True
        buf = bytearray(min(n, _SEND_CHUNK))
        view = memoryview(buf)
        left = n
        try:
            while left > 0:
                r = conn.recv_into(view[: min(left, len(buf))])
                if r == 0:
                    return False
                left -= r
        except OSError:
            return False
        return True

    def _land_payload(self, conn, row, size: int, native) -> int:
        """Land `size` payload bytes into pooled-buffer `row`, rolling
        the CRC32C during the copy-in (fused in `sn_recv_into` when the
        .so is present). Returns the landed CRC; raises NetPlaneError /
        OSError on a torn ingress (connection is then dead)."""
        if size == 0:
            return 0
        if native is not None:
            crc_state = np.zeros(1, np.uint32)
            filled = np.zeros(1, np.uint64)
            out_crcs = np.zeros(2, np.uint32)
            out_counts = np.zeros(1, np.int32)
            got = native.recv_into(
                conn.fileno(), row, size,
                timeout_ms=int(self.request_timeout * 1000),
                granule=size, crc_state=crc_state, filled_state=filled,
                out_crcs=out_crcs, out_counts=out_counts,
            )
            if got != size:
                raise NetPlaneError(f"torn write payload {got}/{size}")
            self.write_native_bytes += got
            M.net_bytes_received_total.inc(
                got, plane="native", direction="write"
            )
            return (
                int(out_crcs[0]) if int(out_counts[0]) > 0
                else int(crc_state[0])
            )
        view = memoryview(row)[:size]
        got = 0
        while got < size:
            r = conn.recv_into(view[got:], size - got)
            if r == 0:
                raise NetPlaneError(f"torn write payload {got}/{size}")
            got += r
        from ..utils.crc import crc32c as _crc

        self.write_python_bytes += size
        M.net_bytes_received_total.inc(
            size, plane="python", direction="write"
        )
        return _crc(row[:size])

    def _serve_write(self, conn, vid, cookie, nid, off_or_crc, size,
                     md) -> bool:
        """Serve one write request; False = connection must close.
        Refused while the fault registry holds points OUTSIDE the write
        path's own chaos namespaces (see write_plane_admissible) — the
        gRPC/HTTP fallback carries that chaos, while the write-path
        crash matrix rides through here."""
        kind = md.get("x-sw-w-kind", "")
        op = md.get("x-sw-w-op", "write")
        refusal = None
        if kind == "needle":
            if size > _MAX_NEEDLE:
                return False  # desynced/hostile frame: drop
            if self.resolve_write is None:
                refusal = "needle writes not served here"
        elif kind == "blob":
            if size > _MAX_BLOB:
                return False
            if self.resolve_blob is None:
                refusal = "blob writes not served here"
        else:
            return False  # unknown kind: protocol desync
        if refusal is None and not write_plane_admissible():
            refusal = "fault registry armed: use the fallback transport"
        if refusal is not None:
            if not self._drain(conn, size):
                return False
            return self._error(conn, refusal)
        self.write_requests += 1
        if kind == "blob":
            return self._serve_blob_write(conn, op, md, off_or_crc, size)
        return self._serve_needle_write(
            conn, vid, cookie, nid, off_or_crc, size, md
        )

    def _serve_needle_write(self, conn, vid, cookie, nid, want_crc,
                            size, md) -> bool:
        from . import native_io

        native = _native_mod() if native_io.enabled() else None
        pool = native_io.landing_pool()
        buf = pool.get(_pool_width(size))
        row = buf[0]
        try:
            try:
                landed_crc = self._land_payload(conn, row, size, native)
            except (OSError, NetPlaneError):
                return False
            if size and landed_crc != (want_crc & 0xFFFFFFFF):
                # payload fully consumed — the stream is in sync, so a
                # refusal (not a drop) lets the client retry/fall back
                return self._error(conn, "write payload CRC mismatch")
            # the one Python-level materialization on this path: the
            # needle record wants bytes it can keep
            data = row[:size].tobytes()
            M.net_bytes_copied_total.inc(
                size, plane="native" if native is not None else "python",
                direction="write",
            )
        finally:
            if buf.shape[1] <= _POOL_MAX_WIDTH:
                pool.put(buf)
        try:
            faults.fire(
                "ec.net.write.before_pwrite",
                volume=vid, needle=nid, size=size,
            )
            stored_size, stored_crc = self.resolve_write(
                vid, nid, cookie, data, md
            )
            faults.fire("ec.net.write.after_pwrite", volume=vid, needle=nid)
        except NetPlaneVolumeRefusal as e:
            return self._error(conn, str(e), status=2)
        except (NetPlaneError, OSError, ValueError) as e:
            return self._error(conn, str(e))
        try:
            conn.sendall(
                _RESP.pack(0, stored_size)
                + _NEEDLE_CRC.pack(stored_crc & 0xFFFFFFFF)
            )
        except OSError:
            return False
        return True

    def _serve_blob_write(self, conn, op, md, off, size) -> bool:
        try:
            path = _unb64(md.get("x-sw-w-path", "")).decode()
        except (ValueError, UnicodeDecodeError):
            path = ""
        try:
            want_crc = int(md.get("x-sw-w-crc", "0"))
        except ValueError:
            want_crc = 0
        do_fsync = md.get("x-sw-w-fsync", "0") == "1"
        try:
            fd = self.resolve_blob(path, op, md)
        except NetPlaneVolumeRefusal as e:
            if not self._drain(conn, size):
                return False
            return self._error(conn, str(e), status=2)
        except (NetPlaneError, OSError) as e:
            if not self._drain(conn, size):
                return False
            return self._error(conn, str(e))
        if fd is None:
            # op handled entirely by the resolver (unlink)
            if not self._drain(conn, size):
                return False
            try:
                conn.sendall(_RESP.pack(0, 0) + _NEEDLE_CRC.pack(0))
            except OSError:
                return False
            return True
        try:
            try:
                faults.fire(
                    "ec.net.write.before_pwrite", path=path, size=size
                )
            except IOError as e:
                if not self._drain(conn, size):
                    return False
                return self._error(conn, str(e))
            from . import native_io

            native = _native_mod() if native_io.enabled() else None
            landed_crc = 0
            if size:
                if native is not None and native.has_recv_file():
                    # socket -> disk with the CRC fused into the landing
                    # loop: Python never touches a payload byte
                    try:
                        got, landed_crc = native.recv_file(
                            conn.fileno(), fd, off, size,
                            timeout_ms=int(self.request_timeout * 1000),
                        )
                    except OSError:
                        return False
                    if got != size:
                        return False
                    self.write_native_bytes += got
                    M.net_bytes_received_total.inc(
                        got, plane="native", direction="write"
                    )
                else:
                    from ..utils.crc import crc32c as _crc

                    chunk = bytearray(min(size, _SEND_CHUNK))
                    view = memoryview(chunk)
                    remaining, o, crc = size, off, 0
                    try:
                        while remaining > 0:
                            want = min(len(chunk), remaining)
                            got = conn.recv_into(view[:want], want)
                            if got == 0:
                                return False
                            crc = _crc(view[:got], crc)
                            os.pwrite(fd, view[:got], o)
                            o += got
                            remaining -= got
                    except OSError:
                        return False
                    landed_crc = crc
                    self.write_python_bytes += size
                    M.net_bytes_received_total.inc(
                        size, plane="python", direction="write"
                    )
                    M.net_bytes_copied_total.inc(
                        size, plane="python", direction="write"
                    )
            if want_crc and landed_crc != (want_crc & 0xFFFFFFFF):
                # corrupt extent is already on disk, but the pushed
                # watermark only advances on an ACK — the client retries
                # the same extent at the same offset
                return self._error(conn, "blob payload CRC mismatch")
            try:
                faults.fire("ec.net.write.after_pwrite", path=path)
                if do_fsync:
                    os.fsync(fd)
            except (IOError, OSError) as e:
                return self._error(conn, str(e))
            try:
                conn.sendall(
                    _RESP.pack(0, size)
                    + _NEEDLE_CRC.pack(landed_crc & 0xFFFFFFFF)
                )
            except OSError:
                return False
            return True
        finally:
            try:
                os.close(fd)
            except OSError:
                pass

    def status(self) -> dict:
        """Sidecar state for /status and /debug/gateway surfaces."""
        return {
            "port": self.port,
            "requests": self.requests,
            "needle_requests": self.needle_requests,
            "sendfile_bytes": self.sendfile_bytes,
            "python_bytes": self.python_bytes,
            "write_requests": self.write_requests,
            "write_native_bytes": self.write_native_bytes,
            "write_python_bytes": self.write_python_bytes,
            "native_serves": self.native_serves,
        }


# ---------------------------------------------------------------------------
# Client
# ---------------------------------------------------------------------------


class PlaneFetch:
    """What became of one range of :meth:`NetPlaneClient.fetch_many_into`.

    `status`: `landed`; `refused` (the holder's `message` read, the
    connection in frame); `short` (answered shorter than asked: the EOF
    clamp); `bad_frame` (longer than asked, or a refusal too long to be
    one); `torn` (the connection failed or closed before the end);
    `timed_out`; `in_flight` (the call returned before it was done);
    `no_plane` (the holder serves no plane: `error` is the memoized
    refusal); `not_asked`. `dst`: the index of the destination it landed
    in; `crcs`: the granule CRCs rolled as it landed, the partial tail's
    last (None without a granule); `n`: the length the holder answered;
    `payload_began`: an answer of the asked length came, so its bytes
    began to land. Clock readings (`time.perf_counter_ns`; 0 where not
    reached): its connection asked for and in hand, its request sent,
    its answer's header parsed, its last byte landed. `peer`: the
    caller's name for the holder, where the caller gives one."""

    __slots__ = (
        "status", "dst", "crcs", "n", "got", "message", "error",
        "payload_began", "t_checkout", "t_connected", "t_sent", "t_header",
        "t_landed", "peer",
    )

    def __init__(self):
        self.status = "not_asked"
        self.dst = self.crcs = self.error = None
        self.n = self.got = 0
        self.message = ""
        self.payload_began = False
        self.t_checkout = self.t_connected = 0
        self.t_sent = self.t_header = self.t_landed = 0
        self.peer = ""

    def failure(self, addr, size: int) -> Exception:
        """What :meth:`NetPlaneClient.read_into` raises for a range that
        did not land."""
        if self.error is not None:
            return self.error
        why = {
            "refused": self.message,
            "short": f"short stream {self.n}/{size}",
            "bad_frame": f"bad frame {self.n}/{size}",
            "timed_out": f"timed out after {self.got}/{size}",
        }.get(self.status, f"torn stream {self.got}/{size}")
        return NetPlaneError(f"{addr}: {why}")


class NetPlaneClient:
    """Pooled client connections to peers' shard net planes, landing
    payload bytes straight in caller buffers (``sn_recv_into``) with the
    fused granule CRC rolled during the copy-in.

    One connection per IN-FLIGHT request, whatever the opcode: a call
    checks a connection to its address out of the pool (dialling one
    where the pool is empty) and back in once the response has been read
    to its end, so the GETs of many HTTP workers that read ranges from
    one holder run side by side and none waits for another's socket. A
    connection whose stream was left torn, short or out of sync is
    closed, never parked. A peer whose plane port refuses the connect
    is memoized and later calls raise :class:`NetPlaneUnavailable`
    immediately — but only for ``unavailable_ttl`` seconds
    (``SEAWEED_EC_NET_PLANE_RETRY_S``, default 30): a sidecar that comes
    up later (rolling restart, late boot) is re-probed and re-adopted
    instead of being written off for the life of the process.
    :meth:`reset` drops the memo immediately (operator hook — e.g.
    right after healing a peer).
    """

    def __init__(self, timeout: float = 30.0, connect_timeout: float = 2.0,
                 unavailable_ttl: float | None = None):
        self.timeout = timeout
        self.connect_timeout = connect_timeout
        if unavailable_ttl is None:
            try:
                unavailable_ttl = float(
                    os.environ.get("SEAWEED_EC_NET_PLANE_RETRY_S", "30")
                )
            except ValueError:
                unavailable_ttl = 30.0
        self.unavailable_ttl = unavailable_ttl
        # the connection pool, per address. Entries are (socket,
        # checkin-time): the server reaps idle connections at its
        # request_timeout (60 s), so anything parked longer than
        # _npool_idle_s is discarded at checkout instead of burning a
        # request on a dead socket (which would silently demote that
        # read to its fallback transport).
        self._npool: dict[
            tuple[str, int], list[tuple[socket.socket, float]]
        ] = {}
        self._npool_max = 16
        self._npool_idle_s = 30.0
        # addr -> monotonic time of the refused connect (TTL'd memo)
        self._no_plane: dict[tuple[str, int], float] = {}
        self._lock = threading.Lock()

    def close(self) -> None:
        with self._lock:
            conns = [s for lst in self._npool.values() for s, _t in lst]
            self._npool.clear()
        for c in conns:
            _close(c)

    def reset(self, addr: tuple[str, int] | None = None) -> None:
        """Forget the no-plane memo for `addr` (or every peer): the
        next call re-probes the connect instead of waiting out the
        TTL."""
        with self._lock:
            if addr is None:
                self._no_plane.clear()
            else:
                self._no_plane.pop(addr, None)

    def _check_memo(self, addr) -> None:
        """Raise if `addr` is inside its no-plane TTL; forget an
        expired refusal so the next connect re-probes (a sidecar that
        has since come up gets re-adopted). Caller holds self._lock."""
        refused_at = self._no_plane.get(addr)
        if refused_at is not None:
            if time.monotonic() - refused_at < self.unavailable_ttl:
                raise NetPlaneUnavailable(f"{addr[0]}:{addr[1]}")
            del self._no_plane[addr]

    def _connect(self, addr) -> socket.socket:
        """Fresh plane connection (no caching); a refused connect is
        memoized for `unavailable_ttl` seconds."""
        try:
            s = socket.create_connection(addr, timeout=self.connect_timeout)
        except OSError as e:
            with self._lock:
                self._no_plane[addr] = time.monotonic()
            raise NetPlaneUnavailable(f"{addr[0]}:{addr[1]}: {e}") from e
        s.settimeout(self.timeout)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def _checkout(self, addr) -> socket.socket:
        """Take a pooled connection (or dial a new one): one connection
        per IN-FLIGHT request, so concurrent reads fan out instead of
        serializing on one socket. Connections parked longer than
        `_npool_idle_s` are discarded — the server side reaps idle
        peers, and a dead pooled socket would cost the next read its
        fast path."""
        stale: list[socket.socket] = []
        fresh = None
        with self._lock:
            self._check_memo(addr)
            lst = self._npool.get(addr)
            now = time.monotonic()
            while lst:
                s, t = lst.pop()
                if now - t < self._npool_idle_s:
                    fresh = s
                    break
                stale.append(s)
        for s in stale:
            _close(s)
        if fresh is not None:
            return fresh
        return self._connect(addr)

    def _checkin(self, addr, s: socket.socket) -> None:
        now = time.monotonic()
        expired: list[socket.socket] = []
        with self._lock:
            lst = self._npool.setdefault(addr, [])
            # reap expired entries from the FRONT (oldest): checkout
            # pops LIFO and stops at the first fresh socket, so without
            # this sweep the old ones below it would pin dead fds (and
            # pool slots) for the life of the process
            while lst and now - lst[0][1] >= self._npool_idle_s:
                expired.append(lst.pop(0)[0])
            if len(lst) < self._npool_max:
                lst.append((s, now))
                s = None  # type: ignore[assignment]
        for dead in expired:
            _close(dead)
        if s is not None:
            _close(s)

    def _release(self, addr, s: socket.socket, healthy: bool) -> None:
        """Back to the pool where the response was read to its end (a
        refusal's too: it leaves the stream in sync); closed where not."""
        if healthy:
            self._checkin(addr, s)
        else:
            _close(s)

    def _request(
        self, addr, vid, sid, gen, off, size, exact: bool = True
    ) -> tuple[socket.socket, int]:
        """Send one range request on a connection checked out for it,
        parse the response header, return (connection positioned at the
        payload, payload length): the caller reads the payload and
        releases the connection. Whatever raises here has released it.
        With `exact` (the default) a server-side EOF clamp raises —
        range callers sized their landing buffer; `exact=False` accepts
        the clamp (whole-shard fetches discover the size this way)."""
        s = self._checkout(addr)
        healthy = False
        try:
            meta = _encode_meta()
            try:
                s.sendall(
                    _REQ.pack(MAGIC, vid, sid, gen, off, size, len(meta))
                    + meta
                )
                head = _recv_exact(s, _RESP.size)
            except (OSError, NetPlaneError) as e:
                raise NetPlaneError(f"{addr}: {e}") from e
            status, n = _RESP.unpack(head)
            if status != 0:
                msg = self._read_refusal(addr, s, n)
                healthy = True  # refusal leaves the stream in sync
                raise NetPlaneError(f"{addr}: {msg}")
            if n > size:
                # the server only ever clamps DOWN (n = min(size, fsize));
                # a longer claim is a desynced or hostile peer — honoring
                # it would stream garbage past the caller's sizing
                raise NetPlaneError(f"{addr}: oversized frame {n}/{size}")
            if exact and n != size:
                # EOF clamp — the gRPC stream's short read. The connection
                # still holds n payload bytes; cheaper to drop it than to
                # drain and resync.
                raise NetPlaneError(f"{addr}: short stream {n}/{size}")
            return s, n
        except BaseException:
            self._release(addr, s, healthy)
            raise

    def read_into(
        self,
        addr: tuple[str, int],
        vid: int,
        sid: int,
        gen: int,
        off: int,
        size: int,
        dst: np.ndarray,
        *,
        granule: int = 0,
    ) -> np.ndarray | None:
        """Land `size` bytes of shard `sid` @`off` DIRECTLY in `dst`
        (1-D C-contiguous uint8: a pooled aligned buffer, a row of a
        reconstruction's matrix). With granule > 0 returns the granule
        CRCs rolled during the copy-in (completed granules plus the
        partial tail) as a u32 ndarray — the caller compares them
        against the .ecsum sidecar with no extra pass over the bytes.
        Calls to one address run side by side, each on a connection of
        its own. :meth:`fetch_many_into` of this one range: with the .so
        one native call from the request to the last byte. Raises
        :class:`NetPlaneUnavailable` for a holder without a plane and
        :class:`NetPlaneError` for a range that did not land."""
        (got,) = self.fetch_many_into(
            [(addr, vid, sid, gen, off, size)], [dst], granule=granule
        )
        if got.status != "landed":
            raise got.failure(addr, size)
        return got.crcs

    def fetch_many_into(
        self, ranges: list, dsts: list[np.ndarray], *, granule: int = 0,
        need: int | None = None, span_of=None,
    ) -> list[PlaneFetch]:
        """Ask for every range at once, each on a connection of its own,
        and land the answers in `dsts` (1-D C-contiguous uint8 buffers),
        handed out in the order the answers arrive, lowest index
        first; return once `need` of them (all, by default) have landed
        whole or none is left to wait for: one :class:`PlaneFetch` a
        range, in order. `ranges`: (addr, vid, sid, gen, off, size) each,
        or None (not asked). `span_of(i)`, where given, is the span whose
        trace context request i carries (else the ambient one's), asked
        for once the range's connection is in hand. Nothing raises for a
        range: each says what became of it. A connection goes back to the
        pool where its answer was read to the end (a refusal's too), and
        is closed where not. With the .so ONE native call sends the
        requests and lands the answers (`sn_plane_fetch`); without it
        Python reads the same wire, a range after another.

        Under a read from a peer (`ec.peer_read`, whose thread has one of
        `trace.TURN_STAGES` open) the check-out is its `conn_checkout`,
        from there to the answer's header its `request_rtt` and the rest
        its `payload_land`."""
        out = [PlaneFetch() for _ in ranges]
        asked = []  # (index, addr, connection, request)
        trace.turn("conn_checkout")
        for i, rg in enumerate(ranges):
            if rg is None:
                continue
            addr, vid, sid, gen, off, size = rg
            o = out[i]
            o.t_checkout = time.perf_counter_ns()
            try:
                s = self._checkout(addr)
            except NetPlaneUnavailable as e:
                o.status, o.error = "no_plane", e
                continue
            finally:
                o.t_connected = time.perf_counter_ns()
            meta = _encode_meta(span=span_of(i) if span_of is not None else None)
            asked.append(
                (i, addr, s, _REQ.pack(MAGIC, vid, sid, gen, off, size, len(meta)) + meta)
            )
        trace.turn("request_rtt")
        if not asked:
            return out
        need = len(asked) if need is None else min(need, len(asked))
        native = _native_mod()
        if native is None:
            self._fetch_python(ranges, dsts, asked, out, granule, need)
            return out
        self._fetch_native(native, ranges, dsts, asked, out, granule, need)
        if len(ranges) == 1 and out[0].payload_began:
            trace.turn_at("payload_land", out[0].t_header)
        return out

    def _fetch_native(self, native, ranges, dsts, asked, out, granule, need):
        """`fetch_many_into`'s requests sent and answers landed in one
        `sn_plane_fetch`."""
        healthy = [False] * len(asked)
        try:
            rec, crcs, msgs = native.plane_fetch(
                [s.fileno() for _i, _a, s, _r in asked],
                [req for _i, _a, _s, req in asked],
                [ranges[i][5] for i, _a, _s, _r in asked],
                dsts, need, granule=granule,
                timeout_ms=int(self.timeout * 1000),
            )
            landed = 0
            for k, (i, _addr, _s, _req) in enumerate(asked):
                (status, answer, n, got, dst, ncrcs, tail, mlen,
                 t_sent, t_hdr, t_land) = rec[k].tolist()
                o = out[i]
                o.status = native.FETCH_STATUS[status]
                o.n, o.got = n, got
                o.t_sent, o.t_header, o.t_landed = t_sent, t_hdr, t_land
                o.payload_began = answer == 0 and n == ranges[i][5]
                if o.status == "landed":
                    o.dst = dst
                    landed += got
                    if granule:
                        o.crcs = crcs[k, :ncrcs].copy()
                        if got % granule:
                            o.crcs = np.append(o.crcs, np.uint32(tail))
                elif o.status == "refused":
                    o.message = msgs[k, :mlen].tobytes().decode(errors="replace")
                healthy[k] = o.status in ("landed", "refused")
            if landed:
                M.net_bytes_received_total.inc(landed, plane="native", direction="read")
        finally:
            for k, (_i, addr, s, _r) in enumerate(asked):
                self._release(addr, s, healthy[k])

    def _fetch_python(self, ranges, dsts, asked, out, granule, need):
        """`fetch_many_into` without the .so: the same wire in Python, a
        range after another, until `need` have landed (those after them
        are not asked)."""
        free = list(range(len(dsts)))
        landed = 0
        for i, addr, s, req in asked:
            o, size = out[i], ranges[i][5]
            healthy = landed >= need  # not asked: in frame as it was
            try:
                if healthy:
                    continue
                s.sendall(req)
                o.t_sent = time.perf_counter_ns()
                status, n = _RESP.unpack(_recv_exact(s, _RESP.size))
                o.t_header, o.n = time.perf_counter_ns(), n
                if status != 0:
                    o.message = self._read_refusal(addr, s, n)
                    o.status, healthy = "refused", True
                elif n != size:
                    o.status = "short" if n < size else "bad_frame"
                else:
                    o.payload_began = True
                    d = next(d for d in free if dsts[d].nbytes >= size)
                    o.crcs = self._land(addr, s, size, dsts[d], granule, None)
                    free.remove(d)
                    o.status, o.dst, o.got, healthy = "landed", d, size, True
                    o.t_landed = time.perf_counter_ns()
                    landed += 1
            except (OSError, NetPlaneError):
                o.status = "torn"
            finally:
                self._release(addr, s, healthy)

    def _land(self, addr, s, size, dst, granule, native):
        """`size` payload bytes of the response `s` stands at, into
        `dst`; -> the granule CRCs rolled on the way (None without
        `granule`). Raises on a torn stream. A read from a peer's
        `payload_land`."""
        trace.turn("payload_land")
        if native is not None:
            crc_state = np.zeros(1, np.uint32)
            filled = np.zeros(1, np.uint64)
            max_out = (size // granule + 2) if granule else 1
            out_crcs = np.zeros(max_out, np.uint32)
            out_counts = np.zeros(1, np.int32)
            got = native.recv_into(
                s.fileno(), dst, size,
                timeout_ms=int(self.timeout * 1000),
                granule=granule, crc_state=crc_state,
                filled_state=filled, out_crcs=out_crcs,
                out_counts=out_counts,
            )
            if got != size:
                raise NetPlaneError(f"{addr}: torn stream {got}/{size}")
            M.net_bytes_received_total.inc(got, plane="native", direction="read")
            if not granule:
                return None
            crcs = list(out_crcs[: int(out_counts[0])])
            if size % granule:
                crcs.append(int(crc_state[0]))
            return np.asarray(crcs, dtype=np.uint32)
        # Python landing (no .so): same buffer, Python recv loop.
        view = memoryview(dst)[:size]
        got = 0
        while got < size:
            r = s.recv_into(view[got:], size - got)
            if r == 0:
                raise NetPlaneError(f"{addr}: torn stream {got}/{size}")
            got += r
        M.net_bytes_received_total.inc(got, plane="python", direction="read")
        if not granule:
            return None
        from ..utils.crc import crc32c as _crc

        return np.array(
            [
                _crc(dst[i : min(i + granule, size)])
                for i in range(0, size, granule)
            ],
            dtype=np.uint32,
        )

    def read_bytes(
        self, addr, vid, sid, gen, off, size
    ) -> bytes:
        """Python-plane fetch over the same wire: materializes the
        payload as `bytes` (counted against the python plane's
        copied/received totals). Nothing in the package calls it: only
        tests/test_native_net_plane.py does, for its same-wire
        Python-plane comparison and the generation fence (ROADMAP
        Design 4)."""
        s, _n = self._request(addr, vid, sid, gen, off, size)
        healthy = False
        try:
            data = _recv_exact(s, size)
            healthy = True
        except (OSError, NetPlaneError) as e:
            raise NetPlaneError(f"{addr}: {e}") from e
        finally:
            self._release(addr, s, healthy)
        M.net_bytes_received_total.inc(size, plane="python", direction="read")
        M.net_bytes_copied_total.inc(size, plane="python", direction="read")
        return data

    def fetch_shard_to_file(
        self, addr, vid, sid, gen, fobj, *, chunk: int = 4 << 20
    ) -> int:
        """Fetch one WHOLE shard (size discovered from the server's EOF
        clamp) into an open binary file object — the migration copy
        path (ec/rebalance.py): the source splices the shard file with
        sendfile(2) and this side lands it through a pooled aligned
        buffer in `chunk`-sized pieces. Returns bytes written. The wire
        bytes are attributed to the native plane
        (`sw_net_bytes_received_total{plane=native}` — or python when
        the .so is absent).
        Raises :class:`NetPlaneUnavailable` (memoized) for peers
        without the sidecar and :class:`NetPlaneError` for refusals
        (stale generation, shard not local) — callers fall back to the
        gRPC CopyFile stream."""
        from . import native_io

        native = _native_mod() if native_io.enabled() else None
        pool = native_io.landing_pool()
        buf = pool.get(chunk)
        row = buf[0]
        total = 0
        s = None
        healthy = False
        try:
            # one request for the whole file: ask for the 4 GiB
            # protocol max and let the server clamp to the size
            s, n = self._request(
                addr, vid, sid, gen, 0, _MAX_REQUEST, exact=False
            )
            while total < n:
                want = min(chunk, n - total)
                try:
                    self._land(addr, s, want, row, 0, native)
                except (OSError, NetPlaneError) as e:
                    raise NetPlaneError(
                        f"{addr}: {e} (after {total}/{n})"
                    ) from e
                fobj.write(row[:want])
                total += want
            healthy = True
        finally:
            if s is not None:
                self._release(addr, s, healthy)
            if buf.shape[1] <= _POOL_MAX_WIDTH:
                pool.put(buf)
        return total

    # ------------------------------------------------------- needle reads

    @staticmethod
    def _read_refusal(addr, s, n: int) -> str:
        """Decode a status!=0 error frame's body (shared by the shard
        and needle paths so the protocol-error handling can't drift).
        Raises NetPlaneError when the frame is desynced (length beyond
        any real refusal string) or the body can't be read — the
        connection is then unusable and the caller must discard it."""
        if n > _MAX_ERROR:
            raise NetPlaneError(f"{addr}: desynced error frame ({n})")
        try:
            return _recv_exact(s, n).decode(errors="replace")
        except (OSError, NetPlaneError) as e:
            raise NetPlaneError(f"{addr}: error body lost ({e})") from e

    def read_needle(
        self, addr: tuple[str, int], vid: int, nid: int, cookie: int
    ) -> bytes:
        """Whole-needle payload over the chunk-read opcode (the warm
        gateway path's filer->volume fetch): the server resolves
        (fd, offset, size, crc) from its needle map and splices the
        payload with sendfile; this side lands it DIRECTLY in a pooled
        4096-aligned buffer via ``sn_recv_into`` with the CRC32C fused
        into the copy-in and verified against the needle's stored CRC —
        a vacuum racing the read, or a stale location, surfaces as a
        mismatch (raise -> caller falls back to HTTP), never as silent
        wrong bytes. Raises :class:`NetPlaneUnavailable` for peers
        without the sidecar (memoized with TTL). Connections come from
        a per-address checkout pool — concurrent warm GETs fan out
        over parallel sockets instead of serializing."""
        s = self._checkout(addr)
        healthy = False
        try:
            meta = _encode_meta()
            try:
                s.sendall(
                    _REQ.pack(
                        MAGIC_NEEDLE, vid, cookie & 0xFFFFFFFF, nid,
                        0, 0, len(meta),
                    )
                    + meta
                )
                head = _recv_exact(s, _RESP.size)
            except (OSError, NetPlaneError) as e:
                raise NetPlaneError(f"{addr}: {e}") from e
            status, n = _RESP.unpack(head)
            if status != 0:
                msg = self._read_refusal(addr, s, n)
                healthy = True  # refusal leaves the stream in sync
                err = NetPlaneError(f"{addr}: {msg}")
                # status 2 = volume-level refusal: callers negative-
                # cache the vid instead of re-probing per chunk
                err.volume_refusal = status == 2
                raise err
            if n > _MAX_NEEDLE:
                raise NetPlaneError(f"{addr}: oversized needle {n}")
            try:
                (want_crc,) = _NEEDLE_CRC.unpack(
                    _recv_exact(s, _NEEDLE_CRC.size)
                )
            except (OSError, NetPlaneError) as e:
                raise NetPlaneError(f"{addr}: {e}") from e
            if n == 0:
                healthy = True
                return b""
            data = self._land_needle(addr, s, int(n), want_crc)
            healthy = True
            return data
        finally:
            self._release(addr, s, healthy)

    # pool width class for an n-byte needle payload (see _pool_width —
    # shared with the server's write landing so the classes can't drift)
    _landing_width = staticmethod(_pool_width)

    def _land_needle(self, addr, s, n: int, want_crc: int) -> bytes:
        from . import native_io

        native = _native_mod() if native_io.enabled() else None
        pool = native_io.landing_pool()
        buf = pool.get(self._landing_width(n))
        row = buf[0]
        try:
            try:
                # one granule as long as the payload: its CRC is the needle's
                (landed_crc,) = self._land(addr, s, n, row, n, native)
            except OSError as e:
                raise NetPlaneError(f"{addr}: {e}") from e
            if int(landed_crc) != (want_crc & 0xFFFFFFFF):
                raise NetPlaneError(f"{addr}: needle CRC mismatch")
            # the one Python-level materialization on this path: pooled
            # landing buffer -> the bytes object the chunk cache keeps
            data = row[:n].tobytes()
            M.net_bytes_copied_total.inc(
                n, plane="native" if native is not None else "python",
                direction="read",
            )
            return data
        finally:
            # a raise out of here (torn stream, CRC mismatch) leaves
            # the caller to close the checked-out socket. Oversized
            # landings never park in the immortal pool.
            if buf.shape[1] <= _POOL_MAX_WIDTH:
                pool.put(buf)

    # ------------------------------------------------------ needle writes

    def _write_request(
        self, addr, vid, sid, gen, off, payload, extra_meta
    ) -> tuple[int, int]:
        """One write-opcode round trip on a pooled connection: header +
        meta + payload out, (status, n [, stored CRC]) back. Returns
        (stored_size, stored_crc). Refusals leave the stream in sync
        (the server drains the payload first), so the connection goes
        back to the pool even on a refusal."""
        s = self._checkout(addr)
        healthy = False
        try:
            meta = _encode_meta(extra_meta)
            try:
                s.sendall(
                    _REQ.pack(
                        MAGIC_WRITE, vid, sid, gen, off,
                        len(payload), len(meta),
                    )
                    + meta
                )
                if payload:
                    s.sendall(payload)
                head = _recv_exact(s, _RESP.size)
            except (OSError, NetPlaneError) as e:
                raise NetPlaneError(f"{addr}: {e}") from e
            status, n = _RESP.unpack(head)
            if status != 0:
                msg = self._read_refusal(addr, s, n)
                healthy = True
                err = NetPlaneError(f"{addr}: {msg}")
                err.volume_refusal = status == 2
                raise err
            try:
                (stored_crc,) = _NEEDLE_CRC.unpack(
                    _recv_exact(s, _NEEDLE_CRC.size)
                )
            except (OSError, NetPlaneError) as e:
                raise NetPlaneError(f"{addr}: {e}") from e
            healthy = True
            from . import native_io

            M.net_bytes_sent_total.inc(
                len(payload),
                plane="native" if native_io.enabled() else "python",
                direction="write",
            )
            return int(n), int(stored_crc)
        finally:
            self._release(addr, s, healthy)

    def write_needle(
        self, addr: tuple[str, int], vid: int, nid: int, cookie: int,
        data: bytes, *, flags: int = 0, name: bytes | str = b"",
        mime: bytes | str = b"", jwt: str = "", fsync: bool = False,
        replicate: bool = True,
    ) -> tuple[int, int]:
        """Append one needle over the write opcode (the PUT path's
        native twin of the ``WriteNeedle`` gRPC / HTTP upload). The
        payload CRC32C rides the header; the server's fused copy-in CRC
        verifies transit, and the ACK's STORED CRC is verified here
        against what was sent — an accepted write certifies the exact
        bytes on disk end to end. Returns (stored_size, stored_crc).
        Raises :class:`NetPlaneUnavailable` (memoized, TTL'd) for peers
        without the sidecar; a refusal with ``volume_refusal=True``
        means the whole volume can never take plane writes here."""
        from ..utils.crc import crc32c as _crc

        crc = _crc(data) if data else 0
        extra = {
            "x-sw-w-kind": "needle",
            "x-sw-w-flags": str(int(flags)),
        }
        if name:
            extra["x-sw-w-name"] = _b64(name)
        if mime:
            extra["x-sw-w-mime"] = _b64(mime)
        if jwt:
            extra["x-sw-w-jwt"] = jwt
        if fsync:
            extra["x-sw-w-fsync"] = "1"
        if not replicate:
            extra["x-sw-w-replicate"] = "0"
        stored_size, stored_crc = self._write_request(
            addr, vid, cookie & 0xFFFFFFFF, nid, crc, data, extra
        )
        if data and stored_crc != crc:
            raise NetPlaneError(
                f"{addr}: stored CRC mismatch "
                f"(ack {stored_crc:#010x} != sent {crc:#010x})"
            )
        return stored_size, stored_crc

    def write_blob(
        self, addr: tuple[str, int], path: str, off: int, data, *,
        fsync: bool = True, jwt: str = "",
    ) -> int:
        """Write one extent of a remote stream-shard blob at `off`
        (kind=blob): the true network transport behind `net:` remote
        roots, replacing the shared-mount assumption. The server lands
        socket->disk (``sn_recv_file``, CRC fused) and fsyncs before
        ACKing when `fsync` — the remote extent is DURABLE once this
        returns. Returns bytes stored."""
        from ..utils.crc import crc32c as _crc

        data = bytes(data)
        extra = {
            "x-sw-w-kind": "blob",
            "x-sw-w-path": _b64(path),
            "x-sw-w-crc": str(_crc(data) if data else 0),
        }
        if fsync:
            extra["x-sw-w-fsync"] = "1"
        if jwt:
            extra["x-sw-w-jwt"] = jwt
        stored, _crc_ack = self._write_request(
            addr, 0, 0, 0, off, data, extra
        )
        return stored

    def unlink_blob(
        self, addr: tuple[str, int], path: str, *, jwt: str = ""
    ) -> None:
        """Remove a remote stream-shard blob (best-effort GC of
        superseded generations)."""
        extra = {
            "x-sw-w-kind": "blob",
            "x-sw-w-op": "unlink",
            "x-sw-w-path": _b64(path),
        }
        if jwt:
            extra["x-sw-w-jwt"] = jwt
        self._write_request(addr, 0, 0, 0, 0, b"", extra)


def make_fetch_into(client: NetPlaneClient, vid: int, generation: int,
                    addr_of=net_addr):
    """Adapt a :class:`NetPlaneClient` to peer_rebuild's injected
    ``fetch_into(peer, sid, off, size, dst, granule)`` transport,
    translating plane exceptions into the rebuild's retry/fallback
    vocabulary (NetPlaneError -> PeerFetchTransient, NetPlaneUnavailable
    -> PeerPlaneUnavailable)."""
    from .peer_rebuild import PeerFetchTransient, PeerPlaneUnavailable

    def fetch_into(peer, sid, off, size, dst, granule):
        try:
            return client.read_into(
                addr_of(peer), vid, sid, generation, off, size, dst,
                granule=granule,
            )
        except NetPlaneUnavailable as e:
            raise PeerPlaneUnavailable(str(e)) from e
        except NetPlaneError as e:
            raise PeerFetchTransient(str(e)) from e

    return fetch_into
