"""Streaming EC: encode-on-write with incremental parity (online RS).

Until now EC only ran as a batch job over SEALED volumes
(`ec/encoder.py:write_ec_files` reads a finished .dat). This module
opens the WRITE path: an :class:`EcStreamEncoder` accepts appends of
unknown total length on a long-lived device stream and keeps parity
trailing the append head by a bounded lag, so redundancy exists while
the object is still being written — EC as a serving-path capability
(the MQ broker's durable-parity log segments, `mq/stream_parity.py`)
instead of a nightly batch.

Why this is cheap math: RS over GF(2^8) is LINEAR. With generator rows
``G = matrix[k:]`` (m x k), parity of a stripe is ``P = G @ D``; when a
row-batch lands in data row ``i`` columns ``[c0,c1)``, the parity of
the zero-extended stripe updates in place::

    P[:, c0:c1] ^= G[:, i:i+1] @ new_bytes      (GF add == XOR)

so a PARTIAL stripe (rows not yet arrived = zeros) always carries valid
parity for its zero-extension — every flush point is a crash-consistent
redundancy point, not just stripe boundaries.

Layout contract (bit-identity with the batch encoder)
-----------------------------------------------------

The stream uses exactly `write_ec_files`'s striping: greedy large
stripes of ``k x block_size`` (row ``i`` of stripe ``s`` lands in shard
``i`` at file offset ``s * block_size``), and — at :meth:`close` with
``finalize=True`` — the ragged tail re-striped with
``small_block_size`` rows, zero-padded, just like the batch path's
small-chunk plan. N appends through the stream therefore produce
byte-identical shard files and sidecar CRCs to ONE `write_ec_files`
over the concatenation with the same block parameters (asserted
cross-backend in tests/test_ec_stream_encode.py).

Durability protocol (the stripe-cursor journal)
-----------------------------------------------

Appends buffer in the open stripe; :meth:`flush` makes them durable:

  1. PROCESS — parity deltas dispatched through the stream's
     DeviceQueue admission (`backend.apply_staged`, PR 5 cost model);
     data rows pwritten at their final offsets; completed stripes seal
     (final parity rows + CRCs).
  2. FSYNC   — every touched shard fd.
  3. JOURNAL — `<base>.stream` cursor (self-checksummed like
     ec/repair_journal.py intents): uuid fence, embedder cookie
     (`meta`, e.g. the MQ partition's base record offset), durable
     byte head, sealed stripe count.

Recovery (:func:`recover_stream`) reads the journal, bounds the head
by on-disk row extents, lets the embedder frame-scan the linear bytes
for the TRUE head (e.g. dense MQ record offsets), then re-derives and
rewrites any parity that disagrees with the data — data is ground
truth; a stripe whose parity disagrees is repaired or rolled back,
never published.

Time-to-durable-parity is the first-class metric:
``sw_ec_stream_parity_lag_seconds`` observes, per append, the wall
time from append() to the flush that made its parity durable;
:meth:`parity_lag_s` exposes the live lag of the oldest un-flushed
append.

Env knobs (`SEAWEED_EC_STREAM_*`, all overridable per call):
``SEAWEED_EC_STREAM_BLOCK_KB`` (large-stripe row block, default 1024),
``SEAWEED_EC_STREAM_SMALL_KB`` (tail re-stripe block, default 64),
``SEAWEED_EC_STREAM_FLUSH_KB`` (broker flush threshold, default 256),
``SEAWEED_EC_STREAM_MAX_LAG_MS`` (broker flush deadline, default 200),
``SEAWEED_EC_STREAM_ROTATE_MB`` (broker stream rotation, default 64),
``SEAWEED_EC_STREAM_BACKEND`` (broker RS backend, default auto).
"""

from __future__ import annotations

import os
import struct
import threading
import time
import uuid as _uuid
import weakref
from dataclasses import dataclass

import numpy as np

from .. import faults
from ..utils import metrics as _M
from ..utils import trace
from ..utils.crc import crc32c
from ..utils.fs import atomic_write, fsync_dir
from ..utils.glog import logger
from .bitrot import BitrotProtection, ShardChecksumBuilder
from .context import (
    BITROT_BLOCK_SIZE,
    BITROT_LEAF_SIZE,
    DEFAULT_EC_CONTEXT,
    ECContext,
    ECError,
)

log = logger("ec.stream")

JOURNAL_SUFFIX = ".stream"

MAGIC = 0x53575354  # "SWST"
FORMAT_VERSION = 1
# magic u32 BE | version u16 | k u8 | m u8 | block u32 | small u32 |
# uuid 16s | meta u64 | durable u64 | sealed u64 | head u64 | crc u32
_JOURNAL = struct.Struct(">I")
_JOURNAL_BODY = struct.Struct("<HBBII16sQQQQ")


def _env_kib(name: str, default_kib: int) -> int:
    try:
        v = int(os.environ.get(name, str(default_kib)))
    except ValueError:
        v = default_kib
    return max(v, 1) << 10


def stream_block_size() -> int:
    """Large-stripe row block (bytes): SEAWEED_EC_STREAM_BLOCK_KB."""
    return _env_kib("SEAWEED_EC_STREAM_BLOCK_KB", 1024)


def stream_small_block_size() -> int:
    """Tail re-stripe block (bytes): SEAWEED_EC_STREAM_SMALL_KB."""
    return _env_kib("SEAWEED_EC_STREAM_SMALL_KB", 64)


_parity_lag = _M.REGISTRY.histogram(
    "sw_ec_stream_parity_lag_seconds",
    "per-append wall time from append() to durable parity "
    "(time-to-durable-parity, the streaming-EC first-class metric)",
    buckets=(
        0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
        10.0, 30.0,
    ),
)
_appended_bytes = _M.REGISTRY.counter(
    "sw_ec_stream_appended_bytes_total",
    "bytes appended to EC stream encoders",
)
_stripes_sealed = _M.REGISTRY.counter(
    "sw_ec_stream_stripes_sealed_total",
    "EC stream stripes sealed (final parity published)",
)
_recovered = _M.REGISTRY.counter(
    "sw_ec_stream_recovered_total",
    "EC stream recovery events by outcome",
    ("outcome",),
)


# Live encoder registry for the open-streams gauge + stream_summary():
# weak, so a dropped encoder never pins device state behind a metric.
_live_streams: "weakref.WeakSet[EcStreamEncoder]" = weakref.WeakSet()


def _open_stream_samples():
    yield {}, float(sum(1 for e in list(_live_streams) if not e.closed))


_M.REGISTRY.gauge(
    "sw_ec_stream_open",
    "EC stream encoders currently open",
    fn=_open_stream_samples,
)


def stream_summary() -> dict:
    """Process-local streaming-EC roll-up for /cluster/status and the
    volume server /status plane (the `/debug/gateway` idiom): open
    streams with their live parity lag, plus the lifetime counters."""
    streams = []
    for enc in list(_live_streams):
        if enc.closed:
            continue
        streams.append(
            {
                "base": os.path.basename(enc.base),
                "head_bytes": enc.head,
                "durable_bytes": enc.durable,
                "sealed_stripes": enc.sealed_stripes,
                "parity_lag_ms": round(enc.parity_lag_s() * 1000.0, 3),
                "chip": enc.chip_label,
            }
        )
    return {
        "open": len(streams),
        "streams": sorted(streams, key=lambda s: s["base"]),
        "appended_bytes": sum(_appended_bytes.snapshot().values()),
        "stripes_sealed": sum(_stripes_sealed.snapshot().values()),
        # label tuples -> plain strings: this dict rides JSON surfaces
        "recovered": {
            (k[0] if k else ""): v
            for k, v in _recovered.snapshot().items()
        },
    }


# --------------------------------------------------------------------------
# Stripe-cursor journal
# --------------------------------------------------------------------------


@dataclass
class StreamJournal:
    """Decoded `<base>.stream` cursor: everything recovery needs to
    trust the on-disk stream prefix."""

    uuid: bytes
    meta: int  # embedder cookie (MQ: base record offset of this stream)
    durable: int  # linear bytes with durable data AND parity
    sealed: int  # stripes whose final parity is published
    head: int  # advisory: bytes appended at journal time (>= durable)
    block_size: int = 0
    small_block_size: int = 0
    data_shards: int = 0
    parity_shards: int = 0

    def to_bytes(self) -> bytes:
        body = _JOURNAL_BODY.pack(
            FORMAT_VERSION,
            self.data_shards,
            self.parity_shards,
            self.block_size,
            self.small_block_size,
            self.uuid,
            self.meta,
            self.durable,
            self.sealed,
            self.head,
        )
        raw = _JOURNAL.pack(MAGIC) + body
        return raw + struct.pack("<I", crc32c(raw))


def load_stream_journal(base: str) -> StreamJournal | None:
    """The stream's cursor, or None when absent/torn — a torn cursor
    means the stream was never durable past its previous cursor (the
    journal is written AFTER the fsync it describes), so recovery
    treats it as empty rather than guessing."""
    path = base + JOURNAL_SUFFIX
    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    want = _JOURNAL.size + _JOURNAL_BODY.size + 4
    if len(raw) != want:
        return None
    if crc32c(raw[:-4]) != struct.unpack("<I", raw[-4:])[0]:
        return None
    if _JOURNAL.unpack_from(raw)[0] != MAGIC:
        return None
    (
        version, k, m, block, small, uid, meta, durable, sealed, head,
    ) = _JOURNAL_BODY.unpack_from(raw, _JOURNAL.size)
    if version != FORMAT_VERSION:
        return None
    return StreamJournal(
        uuid=uid, meta=meta, durable=durable, sealed=sealed, head=head,
        block_size=block, small_block_size=small,
        data_shards=k, parity_shards=m,
    )


# --------------------------------------------------------------------------
# The encoder
# --------------------------------------------------------------------------


class EcStreamEncoder:
    """Online EC encoder for one append stream of unknown length.

    Not thread-safe per method pair by accident: append/flush/close
    serialize on an internal lock, so a broker's append path and its
    background parity flusher may race freely.

    `scheduler` is the QueueScope whose placement/admission config this
    stream runs under (None = process default); the stream is placed
    ONCE at construction via `chip_pool.place_stream` (live-load
    routing) and every parity batch is admitted to the chip's
    DeviceQueue with the PR 5 cost model
    (`batch_cost(m, batch_width)`).

    `meta` is an opaque embedder cookie persisted in the stripe-cursor
    journal (the MQ glue stores the partition's base record offset).
    """

    def __init__(
        self,
        base: str,
        ctx: ECContext = DEFAULT_EC_CONTEXT,
        backend=None,
        block_size: int | None = None,
        small_block_size: int | None = None,
        leaf_size: int = BITROT_LEAF_SIZE,
        scheduler=None,
        meta: int = 0,
        fsync: bool = True,
    ):
        from .backend import get_backend
        from .chip_pool import place_stream
        from .device_queue import batch_cost

        if backend is None:
            backend = get_backend("auto", ctx.data_shards, ctx.parity_shards)
        self.base = base
        self.ctx = ctx
        self.block_size = int(block_size or stream_block_size())
        self.small_block_size = int(
            small_block_size or stream_small_block_size()
        )
        if self.small_block_size > self.block_size:
            raise ECError(
                f"small block {self.small_block_size} exceeds block "
                f"{self.block_size}"
            )
        self.leaf_size = leaf_size
        self.meta = int(meta)
        self.uuid = _uuid.uuid4().bytes
        self._fsync = fsync
        k, m, total = ctx.data_shards, ctx.parity_shards, ctx.total
        self._k, self._m = k, m
        self._stripe_row = self.block_size * k
        # parity generator rows of the shared RS matrix (m x k): the
        # linearity identity needs exactly these coefficients
        self._gen = np.ascontiguousarray(
            np.asarray(backend.matrix, dtype=np.uint8)[k : k + m, :]
        )
        # Two locks so the APPEND path never waits on parity math or
        # fsync: `_buf_lock` guards only the pending buffer + head +
        # lag queue (append takes just this — a buffer copy), while
        # `_lock` serializes process/flush/close (stripe state, fds,
        # journal). Lock order where both are held: _lock outer,
        # _buf_lock inner.
        self._lock = threading.RLock()
        self._buf_lock = threading.Lock()
        self._fds: list[int] = []
        try:
            for i in range(total):
                self._fds.append(
                    os.open(
                        base + ctx.to_ext(i),
                        os.O_RDWR | os.O_CREAT | os.O_TRUNC,
                        0o644,
                    )
                )
        except BaseException:
            for fd in self._fds:
                os.close(fd)
            raise
        self._builders = [
            ShardChecksumBuilder(BITROT_BLOCK_SIZE, leaf_size)
            for _ in range(total)
        ]
        # open-stripe state: data rows + incremental parity, both in
        # memory (k x block + m x block); `filled` is the linear byte
        # count within the stripe
        self._data = np.zeros((k, self.block_size), dtype=np.uint8)
        self._parity = np.zeros((m, self.block_size), dtype=np.uint8)
        self._filled = 0
        self.sealed_stripes = 0
        # appended-but-unprocessed bytes (parity not yet computed)
        self._pending: list[bytes] = []
        self._pending_bytes = 0
        # (linear end offset, append wall time) for lag attribution
        self._lag_q: list[tuple[int, float]] = []
        self.head = 0  # bytes appended
        self._processed = 0  # bytes run through the parity math
        self.durable = 0  # bytes with durable data+parity (journaled)
        self._touched: set[int] = set()
        self.closed = False
        self._finalized = False
        # Flight recorder + placement: one long-lived foreground stream
        self._span = trace.start(
            "ec.stream_encode", name=os.path.basename(base), base=base,
            block_size=self.block_size,
        )
        self._placement = place_stream(
            backend, "foreground",
            scope=scheduler,
            cost_hint=batch_cost(m, self.block_size),
            span=self._span,
        )
        self._backend = self._placement.backend
        self.chip_label = getattr(self._backend, "chip_label", "")
        dq = self._placement.queue
        self._stream = (
            dq.stream("foreground", label="ec stream encode", span=self._span)
            if dq is not None
            else None
        )
        self._write_journal()
        _live_streams.add(self)

    # ------------------------------------------------------------ append

    def append(self, data: bytes) -> int:
        """Buffer `data` at the stream head; returns the linear byte
        offset it starts at. Takes only the buffer lock (one copy) —
        an append never waits behind a concurrent flush's parity math
        or fsync. Parity is computed at the next
        :meth:`process`/:meth:`flush` (the broker's flusher calls flush
        on a bytes/lag policy); durability comes from flush."""
        if not data:
            return self.head
        with self._buf_lock:
            if self.closed:
                raise ECError(f"stream encoder {self.base} is closed")
            off = self.head
            self._pending.append(bytes(data))
            self._pending_bytes += len(data)
            self.head += len(data)
            self._lag_q.append((self.head, time.monotonic()))
            _appended_bytes.inc(len(data))
            return off

    @property
    def pending_bytes(self) -> int:
        with self._buf_lock:
            return self.head - self.durable

    def parity_lag_s(self) -> float:
        """Age of the oldest append whose parity is not yet durable
        (0.0 when fully flushed) — the live lag the flusher bounds."""
        with self._buf_lock:
            if not self._lag_q:
                return 0.0
            return max(time.monotonic() - self._lag_q[0][1], 0.0)

    # ----------------------------------------------------------- process

    def _dispatch_apply(self, coeffs: np.ndarray, batch: np.ndarray):
        """One parity-delta batch through the placed device stream
        (DeviceQueue admission, PR 5 cost model) or directly when the
        scheduler is disabled. Returns the m x width host delta."""
        from .device_queue import batch_cost

        be = self._backend
        if self._stream is None:
            with trace.stage(self._span, "h2d_dispatch", self.chip_label):
                handle = be.apply_staged(coeffs, be.to_device(batch))
            with trace.stage(self._span, "device_drain", self.chip_label):
                return np.ascontiguousarray(be.to_host(handle), np.uint8)
        ticket, handle = self._stream.dispatch(
            lambda: be.apply_staged(coeffs, be.to_device(batch)),
            batch_cost(coeffs.shape[0], batch.shape[-1]),
        )
        try:
            with trace.stage(self._span, "device_drain", self.chip_label):
                return np.ascontiguousarray(be.to_host(handle), np.uint8)
        finally:
            self._stream.release(ticket)

    def _seal_stripe(self) -> None:
        """The open stripe is full: publish its final parity rows, roll
        every shard's CRCs, reset the stripe buffers."""
        faults.fire("ec.stream.seal", base=self.base, stripe=self.sealed_stripes)
        s = self.sealed_stripes
        base_off = s * self.block_size
        k, m = self._k, self._m
        with trace.stage(self._span, "write_sink"):
            for j in range(m):
                os.pwrite(self._fds[k + j], self._parity[j].tobytes(), base_off)
                self._touched.add(k + j)
        for i in range(k):
            self._builders[i].write(self._data[i].tobytes())
        for j in range(m):
            self._builders[k + j].write(self._parity[j].tobytes())
        self._data[:] = 0
        self._parity[:] = 0
        self._filled = 0
        self.sealed_stripes += 1
        _stripes_sealed.inc()

    def process(self) -> None:
        """Drain the append buffer through the parity math: data rows
        pwritten at their final offsets, parity updated in place via
        `apply_staged` (RS linearity), full stripes sealed. Does NOT
        fsync or journal — that is :meth:`flush`'s second half."""
        with self._lock:
            self._process_locked()

    def _process_locked(self) -> None:
        with self._buf_lock:
            if not self._pending:
                return
            buf = b"".join(self._pending)
            self._pending = []
            self._pending_bytes = 0
        self._processed += len(buf)
        block, row_bytes = self.block_size, self._stripe_row
        k = self._k
        pos = 0
        while pos < len(buf):
            in_stripe = self._filled
            row = in_stripe // block
            col = in_stripe % block
            take = min(len(buf) - pos, block - col)
            chunk = np.frombuffer(buf, dtype=np.uint8, count=take, offset=pos)
            # data row into the open-stripe buffer + its final offset
            self._data[row, col : col + take] = chunk
            with trace.stage(self._span, "write_sink"):
                os.pwrite(
                    self._fds[row],
                    buf[pos : pos + take],
                    self.sealed_stripes * block + col,
                )
            self._touched.add(row)
            # incremental parity: P[:, col:col+take] ^= G[:, row] @ chunk
            with trace.stage(self._span, "parity_update"):
                delta = self._dispatch_apply(
                    self._gen[:, row : row + 1], chunk.reshape(1, take)
                )
                self._parity[:, col : col + take] ^= delta
            pos += take
            self._filled += take
            if self._filled == row_bytes:
                self._seal_stripe()

    # ------------------------------------------------------------- flush

    def flush(self) -> int:
        """Make every appended byte durable WITH its parity: process
        the buffer, fsync touched shards, advance the stripe-cursor
        journal, observe per-append time-to-durable-parity. Returns the
        durable head."""
        with self._lock:
            if self.closed:
                return self.durable
            self._process_locked()
            # partial-flush parity for the open stripe: the whole
            # covered column range (rows overwrite columns repeatedly,
            # so per-chunk tracking buys little — the open extent is
            # the honest dirty range)
            if self._filled and self._processed > self.durable:
                block, k = self.block_size, self._k
                full_rows = self._filled // block
                part = self._filled % block
                hi = block if full_rows else part
                base_off = self.sealed_stripes * block
                with trace.stage(self._span, "write_sink"):
                    for j in range(self._m):
                        os.pwrite(
                            self._fds[k + j],
                            self._parity[j, :hi].tobytes(),
                            base_off,
                        )
                        self._touched.add(k + j)
            faults.fire("ec.stream.before_fsync", base=self.base)
            if self._fsync and self._touched:
                with trace.stage(self._span, "fsync_publish"):
                    for i in sorted(self._touched):
                        os.fsync(self._fds[i])
                self._touched.clear()
            faults.fire("ec.stream.before_journal", base=self.base)
            # durable = bytes actually processed+fsynced this cycle;
            # appends racing this flush stay pending for the next one
            self.durable = self._processed
            self._write_journal()
            now = time.monotonic()
            with self._buf_lock:
                while self._lag_q and self._lag_q[0][0] <= self.durable:
                    _, t0 = self._lag_q.pop(0)
                    _parity_lag.observe(max(now - t0, 0.0))
            return self.durable

    def _write_journal(self) -> None:
        j = StreamJournal(
            uuid=self.uuid,
            meta=self.meta,
            durable=self.durable,
            sealed=self.sealed_stripes,
            head=self.head,
            block_size=self.block_size,
            small_block_size=self.small_block_size,
            data_shards=self._k,
            parity_shards=self._m,
        )
        atomic_write(self.base + JOURNAL_SUFFIX, j.to_bytes())

    # ------------------------------------------------------------- close

    def close(self, finalize: bool = True) -> BitrotProtection | None:
        """End the stream.

        ``finalize=True`` re-stripes the ragged tail with small blocks
        (bit-identical to `write_ec_files` over the concatenation),
        publishes the `.ecsum` sidecar, and RETIRES the journal — the
        artifact is now a sealed EC volume layout. ``finalize=False``
        (broker stream rotation) just flushes and closes: the large
        layout + journal stay recoverable."""
        with self._lock:
            if self.closed:
                return None
            prot: BitrotProtection | None = None
            try:
                self.flush()
                if finalize:
                    prot = self._finalize_locked()
            finally:
                # refuse further appends BEFORE the fds go away (the
                # flag is read under the buffer lock on the append path)
                with self._buf_lock:
                    self.closed = True
                for fd in self._fds:
                    try:
                        os.close(fd)
                    except OSError:
                        pass
                self._fds = []
                if self._stream is not None:
                    self._stream.close()
                self._placement.close()
                trace.finish(self._span)
            return prot

    def _finalize_locked(self) -> BitrotProtection:
        ctx = self.ctx
        k, m, block = self._k, self._m, self.block_size
        small = self.small_block_size
        tail_len = self._filled
        if tail_len:
            # the open stripe was written in the LARGE layout for
            # crash recovery; the batch encoder stripes a sub-stripe
            # tail with small rows — rewrite it identically
            base_off = self.sealed_stripes * block
            for fd in self._fds:
                os.ftruncate(fd, base_off)
            tail = b"".join(
                self._data[i].tobytes() for i in range(k)
            )[:tail_len]
            off = 0
            t = 0
            small_row = small * k
            while off < tail_len:
                seg = tail[off : off + small_row]
                mat = np.zeros((k, small), dtype=np.uint8)
                flat = np.frombuffer(seg, dtype=np.uint8)
                mat.reshape(-1)[: len(flat)] = flat
                parity = self._dispatch_apply(self._gen, mat)
                woff = base_off + t * small
                rows = [mat[i].tobytes() for i in range(k)] + [
                    parity[j].tobytes() for j in range(m)
                ]
                with trace.stage(self._span, "write_sink"):
                    for i, row in enumerate(rows):
                        os.pwrite(self._fds[i], row, woff)
                        self._builders[i].write(row)
                        self._touched.add(i)
                off += small_row
                t += 1
            self._data[:] = 0
            self._parity[:] = 0
            self._filled = 0
        faults.fire("ec.stream.before_seal_publish", base=self.base)
        if self._fsync:
            with trace.stage(self._span, "fsync_publish"):
                for fd in self._fds:
                    os.fsync(fd)
            fsync_dir(self.base + ctx.to_ext(0))
        prot = BitrotProtection.from_builders(ctx, self._builders)
        prot.save(self.base + ".ecsum")
        self._finalized = True
        try:
            os.unlink(self.base + JOURNAL_SUFFIX)
            fsync_dir(self.base + JOURNAL_SUFFIX)
        except OSError:
            pass
        return prot

    def __enter__(self) -> "EcStreamEncoder":
        return self

    def __exit__(self, *exc) -> None:
        self.close(finalize=not any(exc))


# --------------------------------------------------------------------------
# Recovery (non-finalized streams: the broker's rotating generations)
# --------------------------------------------------------------------------


def _data_extent_head(
    base: str, ctx: ECContext, block_size: int
) -> int:
    """Largest CONTIGUOUS linear head the on-disk data-row extents can
    support (large-stripe layout). File sizes only ever grow with
    appends, so this is an upper bound on what a frame scan may
    trust."""
    k = ctx.data_shards
    sizes = []
    for i in range(k):
        try:
            sizes.append(os.path.getsize(base + ctx.to_ext(i)))
        except OSError:
            sizes.append(0)
    head = 0
    s = 0
    while True:
        exts = [
            min(max(sz - s * block_size, 0), block_size) for sz in sizes
        ]
        stripe_head = 0
        for e in exts:
            stripe_head += e
            if e < block_size:
                break
        head += stripe_head
        if stripe_head < block_size * k:
            return head
        s += 1


def read_stream_data(
    base: str, ctx: ECContext, block_size: int, lo: int, hi: int
) -> bytes:
    """Linear bytes [lo, hi) of a NON-finalized stream from its
    on-disk data rows (large-stripe layout; absent extents read as
    zeros — the zero-extension recovery verifies against)."""
    if hi <= lo:
        return b""
    k = ctx.data_shards
    row_bytes = block_size * k
    out = bytearray(hi - lo)
    fds = {}
    try:
        pos = lo
        while pos < hi:
            s, rem = divmod(pos, row_bytes)
            row, col = divmod(rem, block_size)
            take = min(hi - pos, block_size - col)
            fd = fds.get(row)
            if fd is None:
                try:
                    fd = os.open(base + ctx.to_ext(row), os.O_RDONLY)
                except OSError:
                    fd = -1
                fds[row] = fd
            if fd >= 0:
                got = os.pread(fd, take, s * block_size + col)
                out[pos - lo : pos - lo + len(got)] = got
            pos += take
    finally:
        for fd in fds.values():
            if fd >= 0:
                os.close(fd)
    return bytes(out)


@dataclass
class StreamRecovery:
    """What :func:`recover_stream` established about one stream."""

    journal: StreamJournal
    head: int  # verified linear head (embedder-framed, parity-repaired)
    data: bytes  # linear bytes [0, head)
    parity_rewritten: int  # stripes whose parity was re-derived
    rolled_back: int  # bytes past `head` discarded


def recover_stream(
    base: str,
    ctx: ECContext | None = None,
    backend=None,
    frame_scan=None,
) -> StreamRecovery | None:
    """Crash-recover a NON-finalized stream.

    Reads the stripe-cursor journal (absent/torn -> None: nothing was
    ever durable under this cursor), bounds the head by the on-disk
    data extents, lets `frame_scan(data) -> head_bytes` trim to the
    embedder's record framing (None accepts the full extent), then
    re-derives parity for every covered stripe and REWRITES any that
    disagrees with the data — data is ground truth, so recovery never
    leaves a stripe whose parity disagrees with its bytes. Bytes past
    the verified head are rolled back (truncated).
    """
    j = load_stream_journal(base)
    if j is None:
        _recovered.inc(outcome="no_journal")
        return None
    if ctx is None:
        ctx = ECContext(j.data_shards, j.parity_shards)
    if (j.data_shards, j.parity_shards) != (ctx.data_shards, ctx.parity_shards):
        _recovered.inc(outcome="config_mismatch")
        return None
    block = j.block_size
    k, m = ctx.data_shards, ctx.parity_shards
    row_bytes = block * k
    hmax = _data_extent_head(base, ctx, block)
    data = read_stream_data(base, ctx, block, 0, hmax)
    head = hmax
    if frame_scan is not None:
        head = min(int(frame_scan(data)), hmax)
        data = data[:head]
    if head < j.durable:
        # fsync promised these bytes; the frames do not reach them —
        # real data loss (torn writes below the cursor), surfaced loud
        log.warning(
            "stream %s: durable cursor %d but only %d bytes recovered",
            base, j.durable, head,
        )
        _recovered.inc(outcome="data_lost")
    if backend is None:
        from .backend import CpuBackend

        backend = CpuBackend(ctx)
    gen = np.ascontiguousarray(
        np.asarray(backend.matrix, dtype=np.uint8)[k : k + m, :]
    )
    # re-derive parity for every covered stripe; rewrite mismatches
    rewritten = 0
    n_stripes = -(-head // row_bytes) if head else 0
    pfds = [
        os.open(base + ctx.to_ext(k + jx), os.O_RDWR | os.O_CREAT, 0o644)
        for jx in range(m)
    ]
    try:
        for s in range(n_stripes):
            lo = s * row_bytes
            seg = data[lo : lo + row_bytes]
            mat = np.zeros((k, block), dtype=np.uint8)
            flat = np.frombuffer(seg, dtype=np.uint8)
            mat.reshape(-1)[: len(flat)] = flat
            want = np.ascontiguousarray(
                backend.apply(gen, mat), dtype=np.uint8
            )
            ok = True
            for jx in range(m):
                have = os.pread(pfds[jx], block, s * block)
                have = have + b"\0" * (block - len(have))
                if have != want[jx].tobytes():
                    ok = False
                    break
            if not ok:
                for jx in range(m):
                    os.pwrite(pfds[jx], want[jx].tobytes(), s * block)
                rewritten += 1
        for fd in pfds:
            os.fsync(fd)
        # roll back data extents past the verified head: a partially
        # written row beyond `head` must not resurface as garbage on
        # the next recovery's extent scan
        rolled = max(hmax - head, 0)
        if rolled:
            for i in range(k):
                path = base + ctx.to_ext(i)
                s, rem = divmod(head, row_bytes)
                row, col = divmod(rem, block)
                try:
                    cur = os.path.getsize(path)
                except OSError:
                    continue
                keep = s * block + (
                    block if i < row else (col if i == row else 0)
                )
                if cur > keep:
                    with open(path, "rb+") as f:
                        f.truncate(keep)
    finally:
        for fd in pfds:
            os.close(fd)
    # the journal reflects the verified state going forward
    j2 = StreamJournal(
        uuid=j.uuid, meta=j.meta, durable=head,
        sealed=head // row_bytes, head=head,
        block_size=block, small_block_size=j.small_block_size,
        data_shards=k, parity_shards=m,
    )
    atomic_write(base + JOURNAL_SUFFIX, j2.to_bytes())
    if rewritten:
        _recovered.inc(rewritten, outcome="parity_rewritten")
    _recovered.inc(outcome="replayed" if head else "rolled_back")
    return StreamRecovery(
        journal=j, head=head, data=data,
        parity_rewritten=rewritten, rolled_back=max(hmax - head, 0),
    )
