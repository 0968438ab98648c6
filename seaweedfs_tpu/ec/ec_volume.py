"""EcVolume: serve reads from mounted EC shards, with on-the-fly
Reed-Solomon recovery of intervals whose shard is absent.

Reference: weed/storage/erasure_coding/ec_volume.go (sealed .ecx binary
search :501, .ecj-backed deletion set :425-455) and store_ec.go
ReadEcShardNeedle/:656-747 (recover-by-reconstruction read path). Remote
shard fetch arrives with the cluster layer; here recovery uses whatever
shards are on local disk.
"""

from __future__ import annotations

import contextlib
import json
import os
import struct
import threading
import time
from typing import NamedTuple, Optional

import numpy as np

from .. import faults
from ..storage.needle import Needle, NeedleError
from ..storage.needle_map import SortedFileNeedleMap
from ..storage.types import actual_offset
from ..utils import metrics as M
from ..utils import trace
from ..utils.chunk_cache import ChunkCache
from ..utils.glog import logger
from ..ops import gf256
from .backend import RSBackend, _decode_coeffs, get_backend
from .bitrot import BitrotError, BitrotProtection
from .context import DEFAULT_EC_CONTEXT, QUARANTINE_SUFFIX, ECContext, ECError
from .decoder import record_actual_size
from .locate import locate_data
from .pipeline import run_staged_apply
from .volume_info import VolumeInfo

log = logger("ec.volume")

# Column-batch width for staged on-the-fly reconstruction: extents at
# least two batches wide go through the backend's staged apply
# (H2D/compute/D2H overlapped per batch); smaller extents take the
# single-shot reconstruct — the latency-sensitive needle-read shape,
# where pipeline thread spawn would cost more than it hides.
STAGED_RECOVERY_BATCH = 4 << 20

# Default byte budget for the reconstructed-interval cache: hot needles
# on a lost shard pay Reed-Solomon + sidecar verification once, not per
# read. Small on purpose — it only ever holds VERIFIED reconstruction
# output for degraded extents. Entries are generation-keyed per shard:
# a shard remount/unmount drops only that shard's extents; content
# changes (tombstones) still drop wholesale.
DEFAULT_INTERVAL_CACHE_BYTES = 16 << 20


# Name prefix of the threads that carry a reconstruction's rows from
# peers (the wait probes sum CPU by class of thread from the name), and
# how many of them a server keeps: its HTTP workers' number.
PEER_FETCH_THREAD_PREFIX = "ec-peer-fetch"
PEER_FETCH_THREADS = 32


def peer_fetch_pool():
    """The pool a reconstruction's fetches from peers run on: made once
    by whoever serves EC volumes (a `Store`; a bare `EcVolume` makes its
    own on first need) and kept, since a thread started is a hand-off a
    GET waits for."""
    from concurrent.futures import ThreadPoolExecutor

    return ThreadPoolExecutor(
        max_workers=PEER_FETCH_THREADS,
        thread_name_prefix=PEER_FETCH_THREAD_PREFIX,
    )


class _PeerAnswer(NamedTuple):
    """A shard range that a peer answered in full."""

    data: "np.ndarray | bytes"  # where the bytes lie
    plane: str  # native | stream: what carried them
    crcs: "np.ndarray | None"  # granule CRCs rolled while they landed


class EcNotFoundError(ECError):
    pass


class EcCookieMismatch(ECError):
    pass


class EcVolume:
    def __init__(
        self,
        directory: str,
        volume_id: int,
        collection: str = "",
        backend_name: str = "auto",
        remote_reader=None,
        interval_cache_bytes: int = DEFAULT_INTERVAL_CACHE_BYTES,
        interval_cache: ChunkCache | None = None,
        scheduler=None,
        fetch_pool=None,
    ):
        """remote_reader(shard_id, offset, size, generation) -> bytes|None
        lets the cluster layer serve shards held by peer servers
        (reference store_ec.go:599 streaming VolumeEcShardRead; the
        generation is the EncodeTsNs fence so a stale peer never answers);
        recovery by local reconstruction remains the fallback. A reader
        with a `peers(shard_id)` method (the volume server's) is asked
        only for shards that some peer holds; one with a `read_into(
        shard_id, offset, size, generation, dst, granule) -> (plane,
        crcs)|None` method (the volume server's) lands a range in the
        buffer where it is used (`_read_from_peer`).

        `interval_cache_bytes` bounds the LRU of verified reconstructed
        extents (0 disables): repeated reads of needles on a missing
        shard reuse one reconstruction instead of re-running RS + CRC
        per read. Entries are keyed by (shard generation, shard id):
        remount/rebuild/unmount of a shard invalidates only that
        shard's extents; deletes invalidate wholesale.

        `interval_cache` (Store wiring) hands in a SHARED ChunkCache:
        one byte budget across every EC volume on the server, so a
        degraded hot volume can use the whole allowance instead of
        being boxed into a per-volume slice while cold volumes' slices
        sit empty. Keys are volume-namespaced; invalidation and close()
        drop only this volume's extents.

        `scheduler` (Store wiring) is the QueueScope whose placement/
        admission config wide degraded reconstructions run under (None
        = the process-wide default scope). `fetch_pool` (Store wiring)
        is the executor a reconstruction's fetches from peers run on
        (None = one of this volume's own, made on first need)."""
        from ..storage.volume import Volume

        self.volume_id = volume_id
        self.collection = collection
        self.base = Volume.base_file_name(directory, collection, volume_id)
        self._lock = threading.RLock()

        vi = VolumeInfo.maybe_load(self.base + ".vif") or VolumeInfo()
        self.version = vi.version
        self.ctx: ECContext = vi.ec_ctx or DEFAULT_EC_CONTEXT
        self.encode_ts_ns = vi.encode_ts_ns  # generation fence

        self._ecx = SortedFileNeedleMap(self.base + ".ecx")
        self._deleted: set[int] = set()
        self._ecj = open(self.base + ".ecj", "ab+")
        self._ecj.seek(0)
        while True:
            b = self._ecj.read(8)
            if len(b) < 8:
                break
            self._deleted.add(struct.unpack(">Q", b)[0])

        # Crash recovery BEFORE serving: a pending <shard>.repair
        # journal means a leaf repair was interrupted mid-protocol —
        # replay (or roll back) it now so no fd ever opens over a
        # half-applied patch (ec/repair_journal.py window table).
        try:
            from .repair_journal import recover_volume_journals

            recover_volume_journals(self.base, self.ctx)
        except Exception as e:  # recovery must never block a mount
            log.error("repair-journal recovery for %s failed: %s", self.base, e)

        self.shard_fds: dict[int, int] = {}
        self._shard_size = 0
        for i in range(self.ctx.total):
            p = self.base + self.ctx.to_ext(i)
            if os.path.exists(p):
                self.shard_fds[i] = os.open(p, os.O_RDONLY)
                self._shard_size = os.path.getsize(p)

        # Authoritative layout from the encode-time .dat size; fallback
        # for .vif-less volumes mirrors the reference's shard-size-1
        # disambiguation (ec_volume.go LocateEcShardNeedleInterval).
        if vi.dat_file_size > 0:
            self._locate_shard_size = vi.dat_file_size // self.ctx.data_shards
        else:
            self._locate_shard_size = max(self._shard_size - 1, 0)

        self.backend: RSBackend = get_backend(
            backend_name, self.ctx.data_shards, self.ctx.parity_shards
        )
        self.remote_reader = remote_reader
        self.scheduler = scheduler
        self._fetch_pool = fetch_pool
        self._owns_fetch_pool = False
        # Bitrot sidecar, loaded lazily for degraded-read verification.
        # False = not loaded yet (absence is re-probed per degraded
        # read; only a successful load is cached).
        self._prot: BitrotProtection | bool = False
        self._prot_warned = False
        # Verified-reconstruction LRU (degraded-read hot path); None =
        # disabled. Keys are VOLUME-NAMESPACED, GENERATION-QUALIFIED
        # shard-aligned extents ("<ns><sid>:<gen>:<lo>:<hi>"), values
        # are bytes that already passed sidecar verification. Each shard
        # id carries its own generation counter, bumped on remount/
        # unmount of THAT shard — an unrelated shard event no longer
        # drops the whole cache, and an in-flight reconstruction racing
        # an invalidation parks its result under the stale generation
        # where no new read looks. The namespace (collection_vid, like
        # the base file name) lets a Store-level shared cache hold many
        # volumes under one byte budget.
        self._cache_ns = (
            f"{collection}_{volume_id}:" if collection else f"{volume_id}:"
        )
        self._shared_cache = interval_cache is not None
        if interval_cache is not None:
            self.interval_cache: ChunkCache | None = interval_cache
        else:
            self.interval_cache = (
                ChunkCache(interval_cache_bytes, tier="ec_interval")
                if interval_cache_bytes > 0
                else None
            )
        self._shard_gen: dict[int, int] = {}
        # Decode-coefficient rows are tiny but their GF inversion isn't
        # free on a hot read path; memoize per (target, source-set).
        self._coeff_cache: dict[tuple, np.ndarray] = {}
        # Observability: total bytes pread/fetched to serve reads
        # (sibling reads during recovery dominate under degraded
        # serving); rides the heartbeat's heat blob and survives a
        # restart in the `.heat` sidecar.
        self.bytes_read = 0
        # Bytes of shard content produced by RS reconstruction (the
        # degraded-read work). Rides the heartbeat telemetry blob as
        # per-volume HEAT: the rebalance scanner (ec/rebalance.py)
        # weighs reconstruction double when ranking hot volumes —
        # moving a reconstructing volume toward chips is exactly what
        # data gravity exists for.
        self.bytes_reconstructed = 0
        # Heat counters survive a clean restart: without the sidecar a
        # restart resets them to zero, the master's per-sweep delta
        # logic sees a counter regression, and the first post-restart
        # window is clamped to zero (worker/control.py) — a whole
        # gravity sweep of real heat lost per restart. The sidecar is
        # generation-fenced on encode_ts_ns so counters from a volume
        # that was re-encoded (same id, new data) are never resurrected.
        self._heat_path = self.base + ".heat"
        try:
            with open(self._heat_path, encoding="utf-8") as f:
                blob = json.load(f)
            if blob.get("gen") == self.encode_ts_ns:
                self.bytes_read = int(blob.get("read_bytes", 0))
                self.bytes_reconstructed = int(
                    blob.get("reconstructed_bytes", 0)
                )
        except (OSError, ValueError):  # absent/corrupt: start cold
            pass

    # ------------------------------------------------------------- lookup

    def find_needle(self, needle_id: int):
        nv = self._ecx.get(needle_id)
        if nv is None:
            return None
        if needle_id in self._deleted:
            return None
        return nv

    def has_needle(self, needle_id: int) -> bool:
        nv = self.find_needle(needle_id)
        return nv is not None and not nv.is_deleted

    # --------------------------------------------------------------- read

    def read_needle(self, needle_id: int, cookie: Optional[int] = None) -> Needle:
        # The laps here, in _read_shard_interval, _recover_interval and
        # _read_extent split the HTTP handler's `volume.read` stage from
        # inside (`.index`, `.shard`, `.recover`, `.parse`): one
        # module-bool check each when disarmed, nothing where the caller
        # has no such stage open.
        trace.lap("index")
        with self._lock:
            nv = self.find_needle(needle_id)
        if nv is None or nv.is_deleted:
            raise EcNotFoundError(f"needle {needle_id:x} not found")
        # Interval reads run OUTSIDE the volume lock: os.pread is
        # thread-safe and a slow remote shard fetch must not serialize
        # every other read of this volume.
        off = actual_offset(nv.offset)
        rec_size = record_actual_size(nv.size, self.version)
        try:
            return self._parse(self._read_extent(off, rec_size), cookie, needle_id)
        except NeedleError as e:
            # The bytes read are rotten (bitrot / torn shard / a peer
            # that answered from the wrong place): the body fails its
            # CRC, or the record's head does not parse or is another
            # needle's. Self-heal on read: re-derive every interval by
            # sidecar-verified reconstruction, bypassing the shard
            # copies. Either the record comes back bit-exact or this
            # raises — a corrupt needle is never served.
            log.warning(
                "needle %x read from shards is not the record (%s); "
                "retrying via verified reconstruction", needle_id, e,
            )
            return self._parse(
                self._read_extent(off, rec_size, prefer_recovery=True),
                cookie, needle_id,
            )

    def _parse(self, raw: bytes, cookie: Optional[int], needle_id: int) -> Needle:
        n = Needle.from_bytes(raw, self.version)
        if n.needle_id != needle_id:
            # the sealed index says which record lies here
            raise NeedleError(
                f"the record at needle {needle_id:x}'s offset says it is "
                f"needle {n.needle_id:x}"
            )
        if cookie is not None and n.cookie != cookie:
            raise EcCookieMismatch(f"needle {needle_id:x} cookie mismatch")
        return n

    def _read_extent(
        self, offset: int, size: int, prefer_recovery: bool = False
    ) -> bytes:
        parts = []
        for iv in locate_data(
            offset, size, self._locate_shard_size, self.ctx.data_shards
        ):
            shard_id, shard_off = iv.to_shard_and_offset(self.ctx.data_shards)
            if prefer_recovery:
                parts.append(self._recover_interval(shard_id, shard_off, iv.size))
            else:
                parts.append(self._read_shard_interval(shard_id, shard_off, iv.size))
        # the join, and the caller's Needle.from_bytes with the body's CRC
        trace.lap("parse")
        return b"".join(parts)

    def _read_shard_interval(
        self, shard_id: int, offset: int, size: int
    ) -> "bytes | np.ndarray":
        trace.lap("shard")
        fd = self.shard_fds.get(shard_id)
        if fd is not None:
            try:
                faults.fire(
                    "ec.volume.shard_read",
                    shard=shard_id, offset=offset, size=size,
                )
                got = os.pread(fd, size, offset)
            except OSError:  # racing unmount closed the fd (or injected)
                got = b""
            got = faults.mutate(
                "ec.volume.shard_read", got,
                shard=shard_id, offset=offset, size=size,
            )
            if len(got) == size:
                self.bytes_read += size
                return got
            # short read = truncated shard; fall through to recovery
        if self.remote_reader is not None and self._peer_lists(shard_id):
            trace.lap("peer")
            sp = trace.current()
            with trace.stage(sp, "peer_read"):
                got = self._read_from_peer("interval", shard_id, offset, size)
            if sp is not None:
                sp.count("peer_reads", 1)
                sp.count("peer_read_bytes", 0 if got is None else size)
                if got is not None and got.plane == "native":
                    sp.count("peer_reads_native", 1)
            if got is not None:
                self.bytes_read += size
                # a buffer of this read's own, which the join takes as it is
                return got.data
        return self._recover_interval(shard_id, offset, size)

    def _peer_lists(self, shard_id: int) -> bool:
        """Whether a read of this shard through `remote_reader` asks a
        peer. The cluster's reader says which peers hold a shard
        (`peers`), so a shard that no live server holds is a look-up
        and no read; a bare callable cannot say and is taken to ask."""
        peers = getattr(self.remote_reader, "peers", None)
        return peers is None or bool(peers(shard_id))

    def _read_from_peer(
        self, kind: str, shard_id: int, offset: int, size: int,
        dst: Optional[np.ndarray] = None, granule: int = 0,
        queued_ns: int = 0,
    ) -> Optional[_PeerAnswer]:
        """[offset, offset+size) of a shard from the peers that hold it,
        or None where none answered in full; counted at this, the
        reader's, side as a read of `kind` (interval | sibling) on the
        plane that carried the answer (native | stream; a read that
        nobody answered counts under `stream`, the transport asked
        last).

        Armed, the read is a span `ec.peer_read` under the ambient one
        (the GET's root for an interval; for a sibling row the
        `ec.degraded_read` whose context the fetch thread runs under)
        and the current span while it lasts, so the holder's
        `rpc.ec_shard_read` names IT as its parent. Its stages lie end
        to end from its start to its end: `fetch_queue` (a sibling
        read's wait for a fetch thread, from `queued_ns`, the
        `perf_counter_ns` of its submit), then `conn_checkout`,
        `request_rtt` and `payload_land`, which the transports turn to
        (`trace.turn`) as they get there. Disarmed: one module-bool
        check."""
        # `peer`: the cluster's reader writes the address it asks, as it
        # asks it (server/volume_server.py _PeerShardReader.read_into)
        sp = trace.start(
            "ec.peer_read", name=f"v{self.volume_id}.{shard_id:02d}",
            kind=kind, shard=shard_id, size=size,
            peer="", plane="stream", answered=0,
        ) if trace.armed else None
        if sp is None:
            return self._ask_peers(kind, shard_id, offset, size, dst, granule)
        got = None
        timer = sp.stage("conn_checkout")
        try:
            with trace.activate(sp), timer:
                # the span starts where its first stage does
                sp.backdate(queued_ns or timer.began_ns, timer.began_cpu_ns)
                if queued_ns:
                    sp.add_interval("fetch_queue", queued_ns, timer.began_ns)
                got = self._ask_peers(
                    kind, shard_id, offset, size, dst, granule
                )
        finally:
            # the reconstruction that asked may have closed this span
            # under the fetch (`unused`): then that stands, whole
            if got is None:
                sp.finish(timer.ended_ns)
            else:
                sp.finish(timer.ended_ns, answered=1, plane=got.plane)
        return got

    def _ask_peers(
        self, kind: str, shard_id: int, offset: int, size: int,
        dst: Optional[np.ndarray], granule: int,
    ) -> Optional[_PeerAnswer]:
        """A reader that offers `read_into` lands the range in `dst` (1-D
        uint8; a fresh buffer where the caller has none) and says which
        plane carried it; with `granule` it hands back the granule
        CRCs that were rolled while the bytes landed, where the plane
        rolls them (None over the stream). A bare callable returns
        `bytes`, which are copied into a `dst` that was given."""
        t0 = time.perf_counter()
        data, plane, crcs = None, "stream", None
        read_into = getattr(self.remote_reader, "read_into", None)
        if read_into is not None:
            if dst is None:
                dst = np.empty(size, dtype=np.uint8)
            got = read_into(
                shard_id, offset, size, self.encode_ts_ns, dst, granule
            )
            if got is not None:
                data, (plane, crcs) = dst, got
        else:
            got = self.remote_reader(shard_id, offset, size, self.encode_ts_ns)
            if got is not None and len(got) == size:
                data = got
                if dst is not None:
                    dst[:] = np.frombuffer(got, dtype=np.uint8)
                    data = dst
        M.ec_peer_reads_total.inc(kind=kind, plane=plane)
        M.ec_peer_read_seconds_total.inc(
            time.perf_counter() - t0, kind=kind, plane=plane
        )
        if data is None:
            return None
        M.ec_peer_read_bytes_total.inc(size, kind=kind, plane=plane)
        return _PeerAnswer(data, plane, crcs)

    def _peer_fetch_pool(self):
        if self._fetch_pool is None:
            with self._lock:
                if self._fetch_pool is None:
                    self._fetch_pool = peer_fetch_pool()
                    self._owns_fetch_pool = True
        return self._fetch_pool

    # ---------------------------------------------------------- recovery

    def _bitrot(self) -> Optional[BitrotProtection]:
        """Lazy-load the .ecsum sidecar for reconstruction verification.
        Absent or unreadable -> None for THIS read only: a successful
        load is cached, but absence is re-probed every time — a sidecar
        that lands late (crash window between shard publish and sidecar
        write, shards copied before the sidecar) must re-arm
        verification, not be disabled for the life of the mount."""
        if self._prot is False:
            try:
                self._prot = BitrotProtection.load(self.base + ".ecsum")
            except (FileNotFoundError, BitrotError, OSError) as e:
                if not self._prot_warned:
                    self._prot_warned = True
                    log.warning(
                        "%s.ecsum unavailable (%s); degraded reads are "
                        "UNVERIFIED until it appears", self.base, e,
                    )
                return None
        return self._prot

    def _recover_interval(self, shard_id: int, offset: int, size: int) -> bytes:
        """Reconstruct [offset, offset+size) of one shard and — when the
        .ecsum sidecar is available — verify the containing bitrot
        granules before returning a byte (the reconstruction itself ran
        over unverified sibling reads, so its output cannot be trusted
        unchecked). Fail-closed: a mismatch raises rather than serving.

        Granularity follows the sidecar: a v2 sidecar's 64 KiB leaves
        mean a needle read reconstructs and verifies only the leaves
        covering its extent, instead of whole 16 MiB blocks (up to 256x
        less sibling I/O per verified degraded read). Verified output
        lands in the interval cache so a hot needle on a lost shard
        pays reconstruction once.
        """
        trace.lap("recover")
        # Flight-recorder root per degraded-read op (a child when a
        # server RPC/scrub span is active in this thread).
        sp = trace.start(
            "ec.degraded_read",
            name=f"v{self.volume_id}.{shard_id:02d}",
            volume=self.volume_id, shard=shard_id,
            offset=offset, size=size,
        )
        try:
            with trace.activate(sp):
                return self._recover_interval_traced(
                    shard_id, offset, size, sp
                )
        finally:
            if sp is not None:
                # a fetch that nobody waits for any more (the matrix was
                # full without it) runs on in its thread: its span ends
                # here with its parent's, at the length it has, and
                # takes nothing more (Span.finish: the first close holds)
                end_ns = time.perf_counter_ns()
                outlived = sum(
                    c.op == "ec.peer_read" and c.finish(end_ns, unused=1)
                    for c in list(sp.children)
                )
                if outlived:
                    sp.count("peer_reads_outlived", outlived)
                sp.finish(end_ns)

    def _recover_interval_traced(
        self, shard_id: int, offset: int, size: int, sp
    ) -> bytes:
        prot = self._bitrot()
        if prot is None or not (0 <= shard_id < len(prot.shard_crcs)):
            return self._reconstruct_range(shard_id, offset, size)
        # Finest level the sidecar records; identical granularity across
        # shards (equal sizes, one layout), so one granule size serves
        # both the sibling pre-checks and the output check.
        bs, _ = prot.verify_granularity(shard_id)
        ssize = prot.shard_sizes[shard_id]
        if offset + size > ssize:
            # extent beyond the sidecar's recorded shard: no ground
            # truth for the tail — serve unverified rather than refuse
            # (matches pre-sidecar volumes)
            return self._reconstruct_range(shard_id, offset, size)
        lo = (offset // bs) * bs
        hi = min(-(-(offset + size) // bs) * bs, ssize)

        cache = self.interval_cache
        key = (
            f"{self._cache_ns}{shard_id}:"
            f"{self._shard_gen.get(shard_id, 0)}:{lo}:{hi}"
        )

        def build() -> bytes:
            # Sources are sidecar-verified BEFORE being fed to
            # Reed-Solomon (a silently-rotten sibling is excluded
            # instead of poisoning the reconstruction, which would
            # force a refusal even though k clean shards exist), and
            # the output before it is served: _reconstruct_range does
            # both against `prot`, granules aligning across shards.
            return self._reconstruct_range(shard_id, lo, hi - lo, prot)

        if cache is None:
            return build()[offset - lo : offset - lo + size]
        # Read-through with singleflight collapse: N concurrent misses
        # on one degraded extent run build() ONCE — everyone gets the
        # leader's verified bytes (the leader's refusal propagates to
        # every waiter too; nobody retries a reconstruction that just
        # failed verification). Only VERIFIED output is ever cached, so
        # a hit is as trustworthy as the read that populated it.
        # Invalidation is race-free both ways it happens: remount/
        # rebuild bump the shard GENERATION (a stale in-flight build
        # parks its bytes under the old key where no new reader looks),
        # and a leaf patch's ranged drop_matching FENCES matching
        # in-flight builds (returned to their callers, never admitted).
        data, src = cache.get_or_load(key, build)
        if src == "hit":
            trace.event(sp, "cache_hit", lo=lo, hi=hi)
        elif src == "wait":
            trace.event(sp, "singleflight_wait", lo=lo, hi=hi)
        return data[offset - lo : offset - lo + size]

    def _sibling_matrix(
        self, shard_id: int, offset: int, size: int, prot, sp
    ) -> tuple[np.ndarray, tuple[int, ...]]:
        """[offset, offset+size) of k sibling shards as the rows of ONE
        contiguous (k, size) matrix, and the shard id in each row
        (reference store_ec.go:656-747). The matrix is what every later
        step takes whole: one native read fills it, one native call
        checks all its rows against the sidecar (`prot`; None = no
        ground truth, rows are taken as read), one put carries it to
        the device. A row that is short, unreadable or rotten is
        refilled from the next mounted shard, then from peers."""
        from . import native_io

        k = self.ctx.data_shards
        matrix = np.empty((k, size), dtype=np.uint8)
        ids: list[int | None] = [None] * k  # shard in each row; None = to fill

        def admit(row: int, shard_ids: list[int], filled_how: str) -> None:
            """Rows from `row` on were just filled with these shards'
            bytes: count them, and keep the ones that pass the sidecar."""
            self.bytes_read += len(shard_ids) * size
            if sp is not None:
                sp.count(filled_how, len(shard_ids))
            ok = [True] * len(shard_ids)
            if prot is not None:
                with trace.stage(sp, "crc_verify"):
                    ok = prot.verify_rows(
                        shard_ids, offset, matrix[row : row + len(shard_ids)]
                    )
            for r, (sid, good) in enumerate(zip(shard_ids, ok), row):
                if good:
                    ids[r] = sid

        # Local sibling reads ride the native zero-copy plane when it's
        # up (and no fault registry is armed — the chaos seams want
        # bytes): the first k mounted siblings land in their rows in one
        # lock-free call. Whatever that leaves open, and everything
        # without the plane, is filled a row at a time.
        use_native = native_io.enabled() and not faults.active()
        rest = [(i, fd) for i, fd in self.shard_fds.items() if i != shard_id]
        if use_native and rest:
            head = rest[:k]
            try:
                with trace.stage(sp, "sibling_read"):
                    native_io.read_batch(
                        [fd for _i, fd in head], [offset] * len(head),
                        matrix[: len(head)], pad_eof=False,
                    )
            except OSError:
                pass  # a short or closed shard; which rows landed is not known
            else:
                rest = rest[k:]
                admit(0, [i for i, _fd in head], "sibling_rows_batched")
        for i, fd in rest:
            if None not in ids:
                break
            row = ids.index(None)
            try:
                with trace.stage(sp, "sibling_read"):
                    if use_native:
                        native_io.read_exact_into(fd, matrix[row], offset)
                    else:
                        got = os.pread(fd, size, offset)
                        if len(got) != size:
                            continue  # truncated shard
                        matrix[row] = np.frombuffer(got, dtype=np.uint8)
            except OSError:
                continue
            admit(row, [i], "sibling_rows_single")
        missing = []
        if None in ids and self.remote_reader is not None:
            missing = [
                i
                for i in range(self.ctx.total)
                if i != shard_id and i not in ids and self._peer_lists(i)
            ]
        if missing:
            matrix = self._fill_from_peers(
                matrix, ids, missing, offset, size, prot, sp
            )
        if None in ids:
            raise ECError(
                f"shard {shard_id} unavailable and only "
                f"{k - ids.count(None)} sibling shards readable (need {k})"
            )
        return matrix, tuple(ids)

    def _fill_from_peers(
        self, matrix: np.ndarray, ids: list, missing: list[int],
        offset: int, size: int, prot, sp,
    ) -> np.ndarray:
        """The open rows of a sibling matrix (`ids[r] is None`) from the
        peers that hold the shards `missing`, every one asked at once and
        every answer landed where it is to be used: the first fetches in
        the open rows themselves, those beyond in spare rows. Returns the
        matrix: the same one where each open row's own fetch filled it;
        else a gathered copy, with spares in the place of rows whose fetch
        failed, came rotten or is still running (a running fetch owns its
        row, so nothing else is written there). `ids` is filled as far as
        good rows came.

        No row enters `ids` unchecked against the sidecar (`prot`; None =
        no ground truth): the granule CRCs that the native shard plane
        rolled while a row landed are compared as it arrives; rows that
        came over a peer's stream wait until enough rows are there and are
        checked under ONE `crc_verify` stage."""
        import contextvars
        from concurrent.futures import FIRST_COMPLETED, wait

        open_rows = [r for r, sid in enumerate(ids) if sid is None]
        need = len(open_rows)
        spare = np.empty((max(0, len(missing) - need), size), dtype=np.uint8)
        dsts = [matrix[r] for r in open_rows] + list(spare)
        granule = prot.verify_granularity(missing[0])[0] if prot is not None else 0
        n_crcs = -(-size // granule) if granule else 0

        def fetch(j, queued_ns):
            return j, self._read_from_peer(
                "sibling", missing[j], offset, size, dsts[j], granule,
                queued_ns,
            )

        pool = self._peer_fetch_pool()
        asked: list = []
        ready: list = []  # answers that came and were not looked at yet
        good: list[int] = []  # fetches whose row is checked, as they came
        unchecked: list[int] = []  # landed over a stream: no CRCs with them
        looked_at = landed = native = 0
        try:
            # "peer_read" covers only the blocked wait on peer fetches.
            # Per-task contextvar copy: the fetch thread sees the caller's
            # request id + active span, so the fetch's `ec.peer_read` is
            # a child of this read's span (its `fetch_queue` begins at
            # the submit) and the peer's span joins the trace whichever
            # plane carries the bytes.
            with trace.stage(sp, "peer_read"):
                for j in range(len(missing)):
                    asked.append(pool.submit(
                        contextvars.copy_context().run, fetch, j,
                        time.perf_counter_ns() if sp is not None else 0,
                    ))
            futures = set(asked)
            # stop as soon as the rows are filled: one hung peer must not
            # stall the read for the full time-out
            while len(good) < need:
                if len(good) + len(unchecked) < need and (ready or futures):
                    if not ready:
                        with trace.stage(sp, "peer_read"):
                            done, futures = wait(
                                futures, return_when=FIRST_COMPLETED
                            )
                        ready.extend(done)
                    looked_at += 1
                    j, got = ready.pop().result()
                    if got is None:
                        continue
                    landed += 1
                    native += got.plane == "native"
                    if prot is None:
                        good.append(j)
                    elif got.crcs is None:
                        unchecked.append(j)
                    elif len(got.crcs) == n_crcs and prot.granules_match(
                        missing[j], offset, got.crcs
                    ):
                        good.append(j)
                    continue
                if not unchecked:
                    break  # every answer is in, and they are too few
                with trace.stage(sp, "crc_verify"):
                    for j in unchecked:
                        (ok,) = prot.verify_rows(
                            [missing[j]], offset, dsts[j][None, :]
                        )
                        if ok:
                            good.append(j)
                unchecked = []
        finally:
            # fetches still waiting for a thread are cancelled; those
            # that run finish unread, and their peers' work is wasted
            for f in asked:
                f.cancel()
            self.bytes_read += landed * size
            if sp is not None:
                started = sum(1 for f in asked if not f.cancelled())
                sp.count("peer_fetches_started", started)
                sp.count("peer_fetches_unused", started - looked_at)
                if native:
                    # checked as they landed, with no call of their own
                    sp.count("sibling_rows_batched", native)
                    sp.count("peer_reads_native", native)
                if landed > native:
                    sp.count("sibling_rows_single", landed - native)
        # rows that lie where they are used before spares
        used = sorted(good, key=lambda j: j >= need)[:need]
        for j in used:
            if j < need:
                ids[open_rows[j]] = missing[j]
        moved = dict(zip(
            (r for r in open_rows if ids[r] is None),
            (j for j in used if j >= need),
        ))
        if moved:
            for r, j in moved.items():
                ids[r] = missing[j]
            matrix = np.stack(
                [dsts[moved[r]] if r in moved else matrix[r] for r in range(len(ids))]
            )
        if sp is not None:
            # 1: a spare stands where an open row's own fetch did not
            # come good in time, and the matrix was gathered once more
            sp.count("matrix_regathers", 1 if moved else 0)
            if used:
                sp.count("sibling_rows_remote", len(used))
        return matrix

    def _decode_row(self, shard_id: int, src_ids: tuple[int, ...]) -> np.ndarray:
        """(1, k) coefficients taking shards `src_ids`, in that order,
        to shard `shard_id`: tiny, but their GF inversion isn't free on
        a hot read path, so memoized per (target, source rows)."""
        coeffs = self._coeff_cache.get((shard_id, src_ids))
        if coeffs is None:
            # the backend already built this matrix (Protocol doesn't
            # promise the attribute, so fall back to constructing)
            k = self.ctx.data_shards
            matrix = getattr(self.backend, "matrix", None)
            if matrix is None:
                matrix = gf256.ReedSolomon(k, self.ctx.parity_shards).matrix
            coeffs = _decode_coeffs(matrix, k, (shard_id,), src_ids)
            if len(self._coeff_cache) >= 64:  # flapping remote sources
                self._coeff_cache.clear()
            self._coeff_cache[(shard_id, src_ids)] = coeffs
        return coeffs

    def _reconstruct_range(
        self, shard_id: int, offset: int, size: int, prot=None
    ) -> bytes:
        """On-the-fly RS decode of one interval from k sibling shards.
        With `prot` (the sidecar; `offset` granule-aligned) every source
        row is verified before it reaches Reed-Solomon and the output
        before it is returned: a mismatch there raises, fail-closed."""
        sp = trace.current()  # the ec.degraded_read root, when armed
        matrix, src_ids = self._sibling_matrix(shard_id, offset, size, prot, sp)
        coeffs = self._decode_row(shard_id, src_ids)
        if size >= 2 * STAGED_RECOVERY_BATCH:
            # Wide extent (multi-leaf verified reconstruction, v1 16 MiB
            # blocks, scrub-driven repair reads): batch the GF(256)
            # apply through the backend's staged hooks so H2D upload,
            # device compute, and D2H drain overlap across column
            # batches — the same shape rebuild uses, one code path
            # (ec/pipeline.py run_staged_apply). One (k, batch) copy at
            # a time is the to_device copy anyway.
            out = np.empty((1, size), dtype=np.uint8)

            def produce():
                for off in range(0, size, STAGED_RECOVERY_BATCH):
                    yield off, np.ascontiguousarray(
                        matrix[:, off : off + STAGED_RECOVERY_BATCH]
                    )

            def consume(off, rec):
                out[0, off : off + rec.shape[1]] = rec[0]

            run_staged_apply(
                self.backend, coeffs, produce, consume,
                describe="ec degraded reconstruction",
                # Degraded reads ARE serving traffic: they preempt any
                # colocated recovery/scrub stream at batch granularity
                # on the shared device queue. On a multi-chip backend
                # the stream lands whole on the least-loaded chip; a
                # 1-row reconstruction's admission cost is ~1/m of a
                # parity encode at equal width (cost model).
                priority="foreground",
                scheduler=self.scheduler,
                cost_hint=size,
                span=sp,
                read_stage="stage_batch",
                write_stage="write_sink",
            )
        else:
            # Single-shot path (the latency-sensitive needle-read
            # shape): the matrix goes up in one put and one apply. Still
            # a CLIENT of the shared per-chip scheduler — serving traffic
            # takes a FOREGROUND window slot with a cost hint, so a
            # gateway read preempts colocated recovery/scrub admission
            # instead of racing it unscheduled (ISSUE 11). The wait
            # lands on the span as "admission_wait", like the staged
            # path's.
            from .device_queue import batch_cost, resolve_scope

            queue = resolve_scope(self.scheduler).for_backend(self.backend)
            slot = (
                queue.admission("foreground", batch_cost(1, size), span=sp)
                if queue is not None
                else contextlib.nullcontext()
            )
            with slot, trace.stage(sp, "reconstruct"):
                out = self.backend.apply(coeffs, matrix)
        self.bytes_reconstructed += size
        if prot is not None:
            with trace.stage(sp, "crc_verify"):
                (ok,) = prot.verify_rows([shard_id], offset, out)
            if not ok:
                raise ECError(
                    f"reconstructed shard {shard_id} [{offset}:{offset + size}) "
                    f"fails .ecsum verification; refusing to serve"
                )
        return out[0].tobytes()

    # ------------------------------------------------------------- delete

    def delete_needle(self, needle_id: int) -> int:
        """Journal an EC tombstone (reference ec_volume_delete.go)."""
        with self._lock:
            nv = self._ecx.get(needle_id)
            if nv is None or nv.is_deleted or needle_id in self._deleted:
                return 0
            self._ecj.write(struct.pack(">Q", needle_id))
            self._ecj.flush()
            os.fsync(self._ecj.fileno())
            self._deleted.add(needle_id)
            self._drop_interval_cache()  # cached extents may cover it
            return nv.size

    # -------------------------------------------------------------- state

    def _drop_interval_cache(self, shard_ids: list[int] | None = None) -> None:
        """Invalidate cached reconstructed extents. With `shard_ids`,
        only THOSE shards' entries drop (and their generation counters
        bump, so an in-flight reconstruction cannot repopulate under the
        old key): a remount of one shard no longer costs every other
        shard's cached reconstructions. None = wholesale for THIS volume
        (content changes — a tombstone may land inside any cached
        extent); a shared Store-level cache keeps other volumes'
        extents either way."""
        if shard_ids is None:
            for sid in range(self.ctx.total):
                self._shard_gen[sid] = self._shard_gen.get(sid, 0) + 1
            if self.interval_cache is not None:
                self.interval_cache.drop_prefix(self._cache_ns)
            return
        for sid in shard_ids:
            self._shard_gen[sid] = self._shard_gen.get(sid, 0) + 1
            if self.interval_cache is not None:
                self.interval_cache.drop_prefix(f"{self._cache_ns}{sid}:")

    def invalidate_shard_ranges(
        self, shard_id: int, ranges: list[tuple[int, int]]
    ) -> None:
        """Drop cached reconstructed extents overlapping the given byte
        ranges of one shard (a leaf repair just patched those bytes in
        place — same inode, so no fd swap, but any cached extent built
        over the old bytes is stale). Finer than a whole-shard
        generation bump: the shard's other cached extents stay hot."""
        if self.interval_cache is None or not ranges:
            return
        prefix = (
            f"{self._cache_ns}{shard_id}:{self._shard_gen.get(shard_id, 0)}:"
        )

        def overlaps(key: str) -> bool:
            try:
                lo, hi = key[len(prefix):].split(":")
                lo, hi = int(lo), int(hi)
            except ValueError:
                return True  # unparseable = assume stale
            return any(lo < rhi and rlo < hi for rlo, rhi in ranges)

        with self._lock:
            self.interval_cache.drop_matching(prefix, overlaps)

    @property
    def shard_ids(self) -> list[int]:
        return sorted(self.shard_fds)

    def quarantined_shards(self) -> list[int]:
        """Shards whose scrub-quarantine file (<shard>.bad) is on disk."""
        return [
            i
            for i in range(self.ctx.total)
            if os.path.exists(self.base + self.ctx.to_ext(i) + QUARANTINE_SUFFIX)
        ]

    def legitimate_shards(self) -> list[int]:
        """Shards this server legitimately owns: currently served PLUS
        quarantined ones (a shard pulled from service for corruption is
        still this server's to repair — it must not drop off the repair
        list just because it was unmounted)."""
        with self._lock:
            held = set(self.shard_fds)
        return sorted(held | set(self.quarantined_shards()))

    def shard_size(self) -> int:
        return self._shard_size

    def refresh_shards(self) -> list[int]:
        """Pick up shard files that appeared on disk since mount (e.g.
        just copied from a peer); returns the current shard ids."""
        with self._lock:
            return self.reopen_shards(
                [i for i in range(self.ctx.total) if i not in self.shard_fds]
            )

    def reopen_shards(self, shard_ids: Optional[list[int]] = None) -> list[int]:
        """Re-open shard fds from the current directory entries. After a
        rebuild atomically replaces a shard file, an fd opened before
        the rename still reads the OLD inode (the quarantined bytes);
        serving must swap to the regenerated file. Returns mounted ids."""
        with self._lock:
            ids = list(self.shard_fds) if shard_ids is None else shard_ids
            self._drop_interval_cache(ids)
            for sid in ids:
                p = self.base + self.ctx.to_ext(sid)
                old = self.shard_fds.pop(sid, None)
                if old is not None:
                    os.close(old)
                if os.path.exists(p):
                    self.shard_fds[sid] = os.open(p, os.O_RDONLY)
                    self._shard_size = max(self._shard_size, os.path.getsize(p))
            return sorted(self.shard_fds)

    def unmount_shards(self, shard_ids: list[int]) -> int:
        """Stop serving specific local shards (reference Unmount per
        shard set); returns how many shards remain mounted."""
        with self._lock:
            self._drop_interval_cache(shard_ids)
            for sid in shard_ids:
                fd = self.shard_fds.pop(sid, None)
                if fd is not None:
                    os.close(fd)
            return len(self.shard_fds)

    def _save_heat(self) -> None:
        """Persist the heat counters beside the volume (atomic tmp +
        rename, best-effort): a clean unmount/restart then resumes the
        monotonic counter stream instead of resetting to zero and
        blanking the master's first post-restart gravity window."""
        try:
            tmp = self._heat_path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(
                    {
                        "gen": self.encode_ts_ns,
                        "read_bytes": int(self.bytes_read),
                        "reconstructed_bytes": int(self.bytes_reconstructed),
                    },
                    f,
                )
            os.replace(tmp, self._heat_path)
        except OSError:  # advisory; never fail a close over heat
            pass

    def close(self) -> None:
        with self._lock:
            self._save_heat()
            if self._owns_fetch_pool:
                self._fetch_pool.shutdown(wait=False, cancel_futures=True)
                self._fetch_pool, self._owns_fetch_pool = None, False
            for fd in self.shard_fds.values():
                os.close(fd)
            self.shard_fds.clear()
            self._ecj.close()
            self._ecx.close()
            if self._shared_cache and self.interval_cache is not None:
                # an unmounted volume must not keep squatting on the
                # store-wide reconstruction budget
                self.interval_cache.drop_prefix(self._cache_ns)
