"""Shared bounded-queue recovery pipeline + fused shard sinks.

Factored out of the encoder (PR 2) so rebuild and decode run the same
4-stage overlap the encode path already enjoyed: disk read (reader
thread) / H2D stage + device dispatch (calling thread) / D2H + disk
write with CRC rolled cache-hot (writer thread), with bounded queues
between stages. Host-side overhead, not the device, dominated encode
e2e before the encoder grew this shape; the serial
read→reconstruct→write loops in rebuild/decode had the same disease.

Shutdown discipline (inherited verbatim from the encoder, where it was
hardened against hung-device postmortems): both worker threads are
JOINED before any caller-owned fd may be closed; on error the abort
event stops the producer (its queue put is abort-aware), the consumer
always drains to the None sentinel, and a thread that refuses to die
raises — truncated output with self-consistent CRCs must never be
reported as success.
"""

from __future__ import annotations

import queue as _queue
import threading as _threading
from typing import Callable, Iterator, Sequence

import numpy as np

from ..utils import trace
from .bitrot import (
    BitrotProtection,
    ShardChecksumBuilder,
)
from .context import BITROT_BLOCK_SIZE, ECContext, ECError


def _traced_produce(span, stage: str, produce):
    """Wrap a producer generator so time spent INSIDE it (disk reads)
    is attributed per batch; time blocked handing batches downstream is
    the queue's to report."""

    def wrapped():
        it = produce()
        while True:
            with span.stage(stage) as timer:
                try:
                    item = next(it)
                except StopIteration:
                    timer.drop()  # the producer's exit read nothing
                    return
            yield item

    return wrapped


def _traced_call(span, stage: str, fn):
    def wrapped(item):
        with span.stage(stage):
            return fn(item)

    return wrapped


QUEUE_SIZE = 2
# What one pipeline can have alive at once: a batch with the reader,
# one with the dispatcher, one with the sink, and the two queues full
# between them. The staged-batch pool (native_io.batch_pool) keeps as
# many matrices and no more.
BATCHES_ALIVE = 2 * QUEUE_SIZE + 3


def run_pipeline(
    produce: Callable[[], Iterator],
    transform: Callable,
    consume: Callable,
    *,
    queue_size: int = QUEUE_SIZE,
    join_timeout: float = 120.0,
    describe: str = "ec pipeline",
    span=None,
    stage_names: tuple = (None, None, None),
) -> None:
    """Run `produce()` items through `transform` then `consume` as three
    overlapped stages.

    - `produce()` is a generator, iterated in a reader thread (disk
      reads happen here, overlapping everything downstream).
    - `transform(item)` runs in the calling thread — the place for
      non-blocking device dispatch (H2D + kernel launch). Its return
      value is handed to `consume`.
    - `consume(result)` runs in a writer thread — the place that may
      BLOCK on device results (to_host) and disk writes, while the
      calling thread keeps dispatching the batches queued behind it.

    Queue residency bound: up to `2*queue_size + 3` items are alive at
    once (one per stage plus the two queues: `BATCHES_ALIVE` at the
    default depth); callers sizing device memory must budget
    accordingly.

    `span` + `stage_names` attribute wall time to the flight recorder
    (utils/trace.py): stage_names is (produce, transform, consume) —
    a None name skips tagging that stage (the caller tags finer-grained
    stages inside its own closure). Time blocked on a FULL bounded
    queue is tagged "queue_wait" (backpressure from the slower
    neighbor), measured only when the put actually blocks. span=None
    (the disarmed tracer) leaves every closure untouched.
    """
    if span is not None:
        if stage_names[0]:
            produce = _traced_produce(span, stage_names[0], produce)
        if stage_names[1]:
            transform = _traced_call(span, stage_names[1], transform)
        if stage_names[2]:
            consume = _traced_call(span, stage_names[2], consume)
    read_q: "_queue.Queue" = _queue.Queue(maxsize=queue_size)
    write_q: "_queue.Queue" = _queue.Queue(maxsize=queue_size)
    abort = _threading.Event()
    errors: list[BaseException] = []

    def _put(q, item) -> bool:
        """Abort-aware put: never blocks forever on a full queue whose
        consumer has stopped."""
        try:
            q.put_nowait(item)
            return True
        except _queue.Full:
            pass
        with trace.stage(span, "queue_wait"):
            while True:
                try:
                    q.put(item, timeout=0.2)
                    return True
                except _queue.Full:
                    if abort.is_set():
                        return False

    def reader():
        try:
            for item in produce():
                if abort.is_set():
                    return
                if not _put(read_q, item):
                    return
        except BaseException as e:  # pragma: no cover - disk errors
            errors.append(e)
            abort.set()
        finally:
            _put(read_q, None)

    def writer():
        try:
            while True:
                item = write_q.get()
                if item is None:
                    return
                consume(item)
        except BaseException as e:  # pragma: no cover - disk errors
            errors.append(e)
            abort.set()
            while write_q.get() is not None:
                pass

    # named: the flight recorder's timeline has a row per thread
    rt = _threading.Thread(target=reader, daemon=True, name="ec-pipe-reader")
    wt = _threading.Thread(target=writer, daemon=True, name="ec-pipe-sink")
    rt.start()
    wt.start()
    try:
        while True:
            item = read_q.get()
            if item is None or abort.is_set():
                break
            if not _put(write_q, transform(item)):
                break
    except BaseException as e:
        errors.append(e)
    finally:
        # JOIN both threads before the caller may close any fd — a
        # reader mid-pread on a closed (possibly reused) fd would read
        # someone else's file. The writer always drains write_q until
        # the None sentinel (its error path keeps consuming), so a
        # BLOCKING put(None) never deadlocks and never drops queued
        # batches on the happy path.
        if errors:
            abort.set()
            try:
                while True:
                    read_q.get_nowait()
            except _queue.Empty:
                pass
        write_q.put(None)
        rt.join(timeout=join_timeout)
        wt.join(timeout=join_timeout)
        if rt.is_alive() or wt.is_alive():  # pragma: no cover
            # A stuck thread (e.g. wedged in a device to_host against a
            # hung device) means the output files are TRUNCATED but
            # any CRC builders are self-consistent with the truncation —
            # returning success here would publish undetectable data
            # loss. Chain the root cause so it isn't masked.
            abort.set()
            raise ECError(
                f"{describe} thread did not finish (producer alive="
                f"{rt.is_alive()}, consumer alive={wt.is_alive()}); "
                f"output is incomplete"
            ) from (errors[0] if errors else None)
    if errors:
        raise errors[0]


def _contiguous(out) -> np.ndarray:
    """The drained batch as the sink needs it; `device_drain.host_copy`
    says what that cost where it copies (a result whose width ended in
    a pad: a tail that does not fill a word, a mesh's equal shares)."""
    trace.lap("host_copy")
    return np.ascontiguousarray(out, dtype=np.uint8)


def run_staged_apply(
    backend,
    coeffs,
    produce: Callable[[], Iterator],
    consume: Callable,
    *,
    queue_size: int = QUEUE_SIZE,
    join_timeout: float = 120.0,
    describe: str = "ec staged apply",
    priority: str = "recovery",
    device_queue="auto",
    scheduler=None,
    cost_hint: int = 0,
    wide: bool = False,
    span=None,
    read_stage: str = "disk_read",
    write_stage: str = "write_sink",
) -> None:
    """The staged device `apply` driver shared by rebuild, decode, and
    degraded reconstruction: run_pipeline where the transform stage is
    `backend.apply_staged(coeffs, backend.to_device(batch))` — a
    NON-BLOCKING H2D upload + device dispatch that also asks for the
    result's copy home (a device backend sends the batch up and brings
    the result back as dense 32-bit words: ec/backend.py) — and the
    writer stage takes the result with `backend.to_host`, which finds
    the bytes on the host or waits for the rest of the copy, before
    handing the host uint8 matrix (a view of what was fetched) to
    `consume`. Batch N computes on the device while batch N+1 uploads
    and batch N-1 comes home behind the sink's writes, the same
    double-buffered window `encode_staged` gave the encoder.

    `produce()` yields `(tag, batch)` pairs; `consume(tag, out)` gets
    the tag back untouched, after `to_host` has returned for the batch
    (offset bookkeeping stays with the caller, and a caller whose
    batches come from `native_io.batch_pool()` carries the matrix in
    the tag and puts it back there: not before, because the runtime
    may read it for the upload until then and `FallbackBackend` replays
    a failed batch from it).
    `coeffs=None` is the pass-through configuration: no device
    round-trip, the batch flows to `consume` unchanged (decode's
    de-stripe, where reads must overlap writes but there is nothing to
    compute).

    The device dispatch is a CLIENT of the shared per-chip scheduler
    (ec/device_queue.py): `priority` tags this stream's class
    (foreground|recovery|scrub) and `device_queue` selects the queue —
    "auto" resolves the stream's PLACEMENT (ec/chip_pool.py: on a
    multi-chip mesh backend the whole stream is routed to the
    least-loaded chip's backend+queue unless `wide` and the pod is
    idle, per `scheduler`'s `ec_placement` mode), an explicit
    DeviceQueue pins one on the given backend (tests), None keeps the
    PR 3 private window. `scheduler` is the QueueScope (None = the
    process-wide default scope); `cost_hint` is the stream's estimated
    total admission cost (rows x bytes) used for least-loaded routing.
    Per-batch admission is cost-denominated (out_rows x width, see
    device_queue.batch_cost), so a 1-row reconstruction stream no
    longer charges like a parity encode. With the scheduler on, the
    chip-wide in-flight bound lives in the queue's window; without it,
    up to ~2*queue_size staged batches are alive at once per call site.

    `span` is the op's flight-recorder span (utils/trace.py; None =
    disarmed): the produce stage is tagged `read_stage` per batch, the
    H2D upload + device dispatch "h2d_dispatch", the blocking to_host
    "device_drain", the consume callback `write_stage`, bounded-queue
    backpressure "queue_wait", and (on the scheduled path) the
    admission wait "admission_wait" — all labeled with the chip the
    stream landed on.
    """
    if coeffs is None:
        run_pipeline(
            produce,
            lambda item: item,
            lambda item: consume(item[0], item[1]),
            queue_size=queue_size,
            join_timeout=join_timeout,
            describe=describe,
            span=span,
            stage_names=(read_stage, None, write_stage),
        )
        return
    coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
    placement = None
    if device_queue == "auto":
        from .chip_pool import place_stream

        placement = place_stream(
            backend, priority,
            scope=scheduler, cost_hint=cost_hint, wide=wide, span=span,
        )
        backend = placement.backend
        device_queue = placement.queue
    chip = getattr(backend, "chip_label", "")

    if device_queue is None:

        def transform(item):
            tag, batch = item
            with trace.stage(span, "h2d_dispatch", chip):
                handle = backend.apply_staged(
                    coeffs, backend.to_device(batch)
                )
            return tag, handle

        def drain(item):
            tag, handle = item
            # Blocks until the device result is ready — while it does,
            # the calling thread keeps dispatching the batches queued
            # behind it.
            with trace.stage(span, "device_drain", chip):
                out = _contiguous(backend.to_host(handle))
            with trace.stage(span, write_stage):
                consume(tag, out)

        try:
            run_pipeline(
                produce,
                transform,
                drain,
                queue_size=queue_size,
                join_timeout=join_timeout,
                describe=describe,
                span=span,
                stage_names=(read_stage, None, None),
            )
        finally:
            if placement is not None:
                placement.close()
        return

    from .device_queue import batch_cost

    out_rows = int(coeffs.shape[0])
    stream = device_queue.stream(priority, label=describe, span=span)

    def transform_q(item):
        tag, batch = item
        width = (
            int(batch.shape[-1])
            if getattr(batch, "ndim", 1) > 1
            else int(getattr(batch, "nbytes", len(batch)))
        )
        ticket, handle = stream.dispatch(
            lambda: backend.apply_staged(coeffs, backend.to_device(batch)),
            batch_cost(out_rows, width),
        )
        return tag, ticket, handle

    def drain_q(item):
        tag, ticket, handle = item
        try:
            with trace.stage(span, "device_drain", device_queue.label):
                out = _contiguous(backend.to_host(handle))
        finally:
            # Success or failure, the window slot frees — a dying stream
            # must not wedge the chip for the other streams.
            stream.release(ticket)
        with trace.stage(span, write_stage):
            consume(tag, out)

    try:
        run_pipeline(
            produce,
            transform_q,
            drain_q,
            queue_size=queue_size,
            join_timeout=join_timeout,
            describe=describe,
            span=span,
            stage_names=(read_stage, None, None),
        )
    finally:
        # Batches parked in an aborted pipeline's write queue never
        # reach drain_q; their slots are released here — and the chip's
        # placement charge drains with the stream.
        stream.close()
        if placement is not None:
            placement.close()


# --------------------------------------------------------------------------
# Shard sinks: the write stage shared by encode and rebuild. Both write
# N parallel byte streams (one per shard file) while rolling the bitrot
# CRCs in the same pass the bytes are cache-hot.
# --------------------------------------------------------------------------


class FusedShardSink:
    """Write stage backed by the STATEFUL native sink (sn_sink_*): one
    GIL-releasing C++ call per batch, a worker thread per shard,
    pwrite(2) at internally-tracked offsets straight from the source
    buffers — no tobytes()/slice copies, and the Python file objects'
    positions are never moved. It exists because most of encode e2e
    wall time was host-side overhead
    (reference equivalent: the single fused encode+CRC loop in
    weed/storage/erasure_coding/ec_encoder.go, and the native volume
    server's byte path the reference grew for the same reason).

    With `leaf_size` set, BOTH sidecar CRC levels come out of ONE
    cache-hot byte pass on the C++ side: leaves are byte-rolled and the
    block level is folded from completed leaf CRCs via the cached
    CRC-shift operator (sn_crc32c_combine) — no Python-side folding,
    no second pass over the bytes.
    `early_writeback` starts background writeback for each just-written
    extent (sync_file_range) so the publish-time fsync drains an
    already-flushing range instead of the whole file — a win on slow
    disks with deep page caches, a loss on filesystems whose write(2)
    is already synchronous (measured -15% on 9p), so it defaults to the
    SEAWEED_EC_EARLY_WB env knob (off unless "1").

    `direct` opts the shard fds into O_DIRECT (page-cache-bypassing)
    writes WHILE every append stays 4096-aligned: the pooled matrices
    are 4096-aligned by construction, so full batches qualify, and the
    ragged tail (or a filesystem that rejects the flag/write — 9p)
    drops that fd back to buffered transparently, bit-identically.
    Defaults to the SEAWEED_EC_ODIRECT env knob (off unless "1"): a win
    for encode/rebuild streams larger than RAM (no page-cache
    eviction storm at fsync), pointless when the page cache absorbs
    the volume anyway.
    """

    def __init__(
        self,
        files: list,
        block_size: int = BITROT_BLOCK_SIZE,
        leaf_size: int = 0,
        early_writeback: bool | None = None,
        direct: bool | None = None,
    ):
        import os as _os

        from ..utils import native

        if early_writeback is None:
            early_writeback = (
                _os.environ.get("SEAWEED_EC_EARLY_WB", "0") == "1"
            )
        if direct is None:
            direct = _os.environ.get("SEAWEED_EC_ODIRECT", "0") == "1"
        if leaf_size and block_size % leaf_size != 0:
            raise ECError(
                f"leaf size {leaf_size} does not divide block size {block_size}"
            )
        self.fds = [f.fileno() for f in files]
        n = len(files)
        self.block_size = block_size
        self.leaf_size = leaf_size
        self._sink = native.NativeSink(
            self.fds, block_size, leaf_size,
            early_writeback=early_writeback, direct=direct,
        )
        self.crcs: list[list[int]] = [[] for _ in range(n)]
        self._leaf_crcs: list[list[int]] = [[] for _ in range(n)]
        self.sizes = [0] * n
        self._out: tuple | None = None
        self._finished = False
        self._direct_flags = None

    def direct_flags(self):
        """Per-shard O_DIRECT engagement (u8[n], 1 = still direct) —
        whether the page-cache bypass survived this stream's alignment;
        all-zero when SEAWEED_EC_ODIRECT is off or the fs refused.
        Snapshotted at finish (the native handle is freed there)."""
        if self._direct_flags is not None:
            return self._direct_flags
        return self._sink.direct_flags()

    def append_rows(self, rows: Sequence[np.ndarray]) -> None:
        """Append one equal-width batch to every shard stream; rows[i]
        goes to fds[i]. Rows must be 1-D C-contiguous uint8 (row views
        of a contiguous matrix qualify — no copies are made), and must
        stay alive until this call returns (the C side writes straight
        from them)."""
        n = len(self.fds)
        if len(rows) != n:
            raise ECError(f"expected {n} rows, got {len(rows)}")
        if self._finished:
            raise ECError("shard sink already finished")
        width = len(rows[0])
        if any(len(r) != width for r in rows):
            raise ECError("shard sink rows have unequal widths")
        granule = self.leaf_size or self.block_size
        max_out = width // granule + 2
        out = self._out
        if out is None or out[0].shape[1] < max_out:
            out = (
                np.empty((n, max_out), np.uint32),  # block crcs
                np.empty(n, np.int32),
                np.empty((n, max_out), np.uint32),  # leaf crcs
                np.empty(n, np.int32),
            )
            self._out = out
        ptrs = []
        for r in rows:
            if not (r.flags.c_contiguous and r.dtype == np.uint8):
                raise ECError("shard sink rows must be contiguous uint8")
            ptrs.append(r.ctypes.data)
        obc, obn, olc, oln = out
        # overflow (count -1) cannot reach here: the C side flags the
        # shard failed and NativeSink.append raises OSError first
        self._sink.append(ptrs, width, obc, obn, olc, oln)
        for i in range(n):
            c = int(obn[i])
            if c:
                self.crcs[i].extend(int(x) for x in obc[i, :c])
            if self.leaf_size:
                c = int(oln[i])
                if c:
                    self._leaf_crcs[i].extend(int(x) for x in olc[i, :c])
            self.sizes[i] += width

    def _finish(self) -> None:
        if self._finished:
            return
        self._finished = True
        self._direct_flags = self._sink.direct_flags()
        tb, tbv, tl, tlv, _sizes = self._sink.finish()
        for i in range(len(self.fds)):
            if tbv[i]:
                self.crcs[i].append(int(tb[i]))
            if self.leaf_size and tlv[i]:
                self._leaf_crcs[i].append(int(tl[i]))
        self._sink.destroy()

    def block_crcs(self) -> list[list[int]]:
        self._finish()
        return [list(c) for c in self.crcs]

    def leaf_crcs(self) -> list[list[int]]:
        self._finish()
        return [list(c) for c in self._leaf_crcs] if self.leaf_size else []

    def to_protection(self, ctx: ECContext) -> BitrotProtection:
        import uuid as _uuid

        return BitrotProtection(
            ctx=ctx,
            block_size=self.block_size,
            uuid=_uuid.uuid4().bytes,
            shard_sizes=list(self.sizes),
            shard_crcs=self.block_crcs(),
            leaf_size=self.leaf_size,
            shard_leaf_crcs=self.leaf_crcs(),
        )


class PyShardSink:
    """Pure-Python fallback write stage (native .so unavailable, or a
    byte-mutating fault point needs materialized bytes)."""

    def __init__(
        self,
        files: list,
        block_size: int = BITROT_BLOCK_SIZE,
        leaf_size: int = 0,
    ):
        self.files = files
        self.block_size = block_size
        self.leaf_size = leaf_size
        self.builders = [
            ShardChecksumBuilder(block_size, leaf_size) for _ in files
        ]

    @property
    def sizes(self) -> list[int]:
        return [b.total for b in self.builders]

    def append_rows(self, rows: Sequence) -> None:
        if len(rows) != len(self.files):
            raise ECError(f"expected {len(self.files)} rows, got {len(rows)}")
        for i, (f, row) in enumerate(zip(self.files, rows)):
            b = row if isinstance(row, (bytes, bytearray)) else np.asarray(
                row, dtype=np.uint8
            ).tobytes()
            mv = memoryview(b)
            while mv:  # raw FileIO may short-write
                mv = mv[f.write(mv) :]
            self.builders[i].write(b)

    def block_crcs(self) -> list[list[int]]:
        return [b.finish() for b in self.builders]

    def leaf_crcs(self) -> list[list[int]]:
        if not self.leaf_size:
            return []
        return [b.finish_leaves() for b in self.builders]

    def to_protection(self, ctx: ECContext) -> BitrotProtection:
        return BitrotProtection.from_builders(ctx, self.builders)


def make_shard_sink(
    files: list,
    block_size: int = BITROT_BLOCK_SIZE,
    leaf_size: int = 0,
    prefer_fused: bool = True,
) -> FusedShardSink | PyShardSink:
    """Fused native sink when the .so is available (and the native
    plane isn't disabled via SEAWEED_EC_NATIVE=0), Python otherwise."""
    from . import native_io

    if prefer_fused and native_io.enabled():
        try:
            return FusedShardSink(files, block_size, leaf_size)
        except Exception:
            pass
    return PyShardSink(files, block_size, leaf_size)
