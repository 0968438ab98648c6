"""EC encode: volume (.dat + .idx) -> .ec00.. shards, .ecx, .ecsum, .vif.

Reference pipeline: weed/storage/erasure_coding/ec_encoder.go
(WriteEcFiles / encodeDatFile / encodeDataOneBatch) and the server RPC
VolumeEcShardsGenerate (volume_grpc_erasure_coding.go:45), which writes
the .ecx BEFORE the shards to close a write race, then persists .ecsum
and .vif.

TPU-first divergence: the reference feeds its SIMD encoder 256KB
buffers; a device wants batches in the tens of MB. Because parity is
columnwise-independent, any batch split of a stripe row produces
bit-identical shards, so the backend is fed `batch_size` columns at a
time (default 16 MiB per shard => 160 MiB device input at 10+4) and the
shard files/CRC builders are appended chunk by chunk in offset order.
"""

from __future__ import annotations

import os
import time

import numpy as np

from .. import faults
from ..storage.needle_map import MemDb
from ..utils import trace
from .backend import RSBackend, get_backend
from .bitrot import BitrotProtection
from .context import (
    BITROT_LEAF_SIZE,
    LARGE_BLOCK_SIZE,
    SMALL_BLOCK_SIZE,
    DEFAULT_EC_CONTEXT,
    ECContext,
    ECError,
)
from .pipeline import make_shard_sink, run_pipeline
from .volume_info import VolumeInfo

DEFAULT_BATCH = 16 * 1024 * 1024

# A stream at least this large (source bytes: the .dat for encode,
# k x shard extent for rebuild) counts as "wide" for placement: a lone
# wide stream on an idle pod keeps the column-mesh slicing (all chips
# on one stream); anything smaller — or any stream with competitors —
# is placed whole onto the least-loaded chip (ec/chip_pool.py,
# `ec_placement=auto`).
WIDE_STREAM_BYTES = 1 << 30


def _pread_padded(fd: int, buf: np.ndarray, offset: int) -> None:
    """Fill `buf` from fd at `offset` IN PLACE (no intermediate bytes
    object), zero-padding past EOF."""
    mv = memoryview(buf)
    filled = 0
    want = len(buf)
    while filled < want:
        got = os.preadv(fd, [mv[filled:]], offset + filled)
        if got == 0:
            break
        filled += got
    if filled < want:
        buf[filled:] = 0


def write_sorted_file_from_idx(base: str, ext: str = ".ecx") -> None:
    """Convert write-ordered .idx -> sorted sealed index (reference
    WriteSortedFileFromIdx, ec_encoder.go:32-59)."""
    db = MemDb()
    db.load_idx(base + ".idx")
    db.write_sorted_file(base + ext)


def write_ec_files(
    base: str,
    ctx: ECContext = DEFAULT_EC_CONTEXT,
    backend: RSBackend | None = None,
    batch_size: int = DEFAULT_BATCH,
    large_block_size: int = LARGE_BLOCK_SIZE,
    small_block_size: int = SMALL_BLOCK_SIZE,
    leaf_size: int = BITROT_LEAF_SIZE,
    scheduler=None,
) -> BitrotProtection:
    """Stripe+encode base.dat into base.ec00..; returns bitrot CRCs
    accumulated during the same pass. `leaf_size` > 0 additionally rolls
    the v2 sidecar's per-leaf CRCs (same pass, same bytes); 0 emits a
    v1 (block-level only) sidecar. `scheduler` is the QueueScope whose
    placement/admission config this encode stream runs under (None =
    the process-wide default)."""
    if backend is None:
        backend = get_backend("auto", ctx.data_shards, ctx.parity_shards)
    k, total = ctx.data_shards, ctx.total

    dat_fd = os.open(base + ".dat", os.O_RDONLY)
    outputs: list = []
    # Flight-recorder span for the encode pipeline (a child when called
    # under ec_encode_volume's root; its own root for direct callers).
    sp = trace.start(
        "ec.encode", name=os.path.basename(base), base=base,
        batch_size=batch_size,
    )
    try:
        for i in range(total):
            # buffering=0: the fused native sink writes via raw fds; the
            # Python fallback writes whole >=1MiB batches, where a
            # userspace buffer adds a copy and saves nothing.
            outputs.append(open(base + ctx.to_ext(i), "wb", buffering=0))
        sink = make_shard_sink(outputs, leaf_size=leaf_size)
        dat_size = os.fstat(dat_fd).st_size
        large_row = large_block_size * k
        small_row = small_block_size * k

        # Row/chunk schedule: the hot loop is disk-bound (SURVEY.md hard
        # part (b)), so reads, H2D staging, device encode, and shard
        # writes run as the shared 4-stage pipeline (ec/pipeline.py) —
        # the device computes batch N while batch N+1 is read/transferred
        # and batch N-1 drains to host and disk.
        def chunk_plan():
            processed = 0
            remaining = dat_size
            while remaining >= large_row:
                yield processed, large_block_size
                processed += large_row
                remaining -= large_row
            while remaining > 0:
                yield processed, small_block_size
                processed += small_row
                remaining -= small_row

        def batch_plan():
            """(row_offset, block_size, chunk_off, width) per batch."""
            for row_offset, block_size in chunk_plan():
                batch = min(batch_size, block_size)
                for chunk_off in range(0, block_size, batch):
                    yield (
                        row_offset, block_size, chunk_off,
                        min(batch, block_size - chunk_off),
                    )

        # Native read source (ec/native_io.py): one GIL-releasing
        # batched pread per batch straight into an aligned matrix of
        # the process-wide pool, which flows read -> device -> sink
        # untouched (the zero-copy plane), with the NEXT batch's extents
        # readahead-hinted before this one reads. An armed fault
        # registry or SEAWEED_EC_NATIVE=0 keeps the bit-identical
        # Python preadv loop.
        from . import native_io

        use_native = native_io.enabled() and not faults.active()
        pool = native_io.batch_pool() if use_native else None

        def produce():
            plan = list(batch_plan())
            for n_batch, (row_offset, block_size, chunk_off, width) in (
                enumerate(plan)
            ):
                with trace.stage(sp, "disk_read"):
                    offsets = [
                        row_offset + i * block_size + chunk_off
                        for i in range(k)
                    ]
                    trace.count("read_bytes", k * width)
                    if use_native:
                        if n_batch + 1 < len(plan):
                            nro, nbs, nco, nw = plan[n_batch + 1]
                            for i in range(k):
                                native_io.prefetch(
                                    dat_fd, nro + i * nbs + nco, nw
                                )
                        data, held = pool.get(k, width)
                        if held:
                            trace.count("read_reused_bytes", k * width)
                        native_io.read_batch(
                            [dat_fd] * k, offsets, data, pad_eof=True
                        )
                    else:
                        data = np.empty((k, width), dtype=np.uint8)
                        for i in range(k):
                            _pread_padded(dat_fd, data[i], offsets[i])
                yield data

        # Encode is SERVING traffic: it dispatches as a foreground
        # stream of the shared per-chip scheduler (ec/device_queue.py),
        # so a colocated background rebuild yields the H2D slot at
        # every batch boundary instead of head-of-line-blocking the
        # encode. On a multi-chip backend the WHOLE stream is placed
        # onto the least-loaded chip (ec/chip_pool.py) — only a huge
        # lone encode on an idle pod keeps the column-mesh slicing.
        # Scheduler disabled -> the PR 3 private window on the original
        # backend.
        from .chip_pool import place_stream
        from .device_queue import batch_cost

        m = ctx.parity_shards
        placement = place_stream(
            backend, "foreground",
            scope=scheduler,
            # total admission cost this stream will dispatch: m output
            # rows per column of the per-shard extent
            cost_hint=batch_cost(m, -(-dat_size // k)),
            wide=dat_size >= WIDE_STREAM_BYTES,
            span=sp,
        )
        enc_backend = placement.backend
        dq = placement.queue
        stream = (
            dq.stream("foreground", label="ec encode", span=sp)
            if dq is not None
            else None
        )
        chip = getattr(enc_backend, "chip_label", "")

        def transform(data):
            # H2D stage + device encode dispatch, both async: device
            # residency bound is ~4 batches alive at once (one draining
            # in to_host, two queued, one being dispatched), so peak
            # device memory is ~4x batch_size of input (+ m/k of that
            # in outputs); callers raising batch_size must budget
            # accordingly. With the shared scheduler the chip-wide
            # bound is the queue's window instead.
            if stream is None:
                with trace.stage(sp, "h2d_dispatch", chip):
                    handle = enc_backend.encode_staged(
                        enc_backend.to_device(data)
                    )
                return data, None, handle
            ticket, handle = stream.dispatch(
                lambda: enc_backend.encode_staged(enc_backend.to_device(data)),
                batch_cost(m, data.shape[1]),
            )
            return data, ticket, handle

        def consume(item):
            data, ticket, parity_handle = item
            # Blocks until the device result is ready — while it does,
            # the main thread keeps dispatching H2D+encode for the
            # batches queued behind this one.
            try:
                with trace.stage(sp, "device_drain", chip):
                    parity = np.ascontiguousarray(
                        enc_backend.to_host(parity_handle), dtype=np.uint8
                    )
            finally:
                if ticket is not None:
                    stream.release(ticket)
            with trace.stage(sp, "write_sink"):
                sink.append_rows([*data, *parity])
            if pool is not None:
                # to_host has returned and the batch's bytes are with
                # the sink: its matrix is free to carry a later batch
                pool.put(data)

        try:
            run_pipeline(
                produce,
                transform,
                consume,
                # Join bound: up to ~4 batches can still be draining (one
                # in to_host, two queued, one dispatched); allow each
                # 16 MiB/s of slow-disk write plus a fixed device-fetch
                # allowance.
                join_timeout=60.0 + 4.0 * batch_size / (16 << 20),
                describe="ec encode pipeline",
                span=sp,
            )
        finally:
            if stream is not None:
                stream.close()
            placement.close()

        # Crash window: shards fully written but not yet durable — a
        # power cut here may leave any suffix of any shard missing.
        faults.fire("ec.encode.before_fsync", base=base)
        # Durability barrier. Flushes are issued in parallel: on a real
        # disk array the 14 shard files' dirty pages drain concurrently
        # instead of serializing 14 round-trips.
        from concurrent.futures import ThreadPoolExecutor as _TPE

        with trace.stage(sp, "fsync_publish"):
            for f in outputs:
                f.flush()
            with _TPE(max_workers=len(outputs)) as ex:
                list(ex.map(lambda f: os.fsync(f.fileno()), outputs))
    finally:
        os.close(dat_fd)
        for f in outputs:
            f.close()
        trace.finish(sp)
    from ..utils.fs import fsync_dir

    fsync_dir(base + ".dat")
    return sink.to_protection(ctx)


def ec_encode_volume(
    base: str,
    ctx: ECContext = DEFAULT_EC_CONTEXT,
    backend: RSBackend | None = None,
    batch_size: int = DEFAULT_BATCH,
    version: int = 3,
    leaf_size: int = BITROT_LEAF_SIZE,
    scheduler=None,
) -> VolumeInfo:
    """Full encode of one volume's files (the server-side work of
    VolumeEcShardsGenerate). Order matters: .ecx first (write-race
    close, volume_grpc_erasure_coding.go:107-116), then shards, then
    .ecsum + .vif."""
    if not os.path.exists(base + ".dat"):
        raise ECError(f"{base}.dat not found")
    if not os.path.exists(base + ".idx"):
        raise ECError(f"{base}.idx not found")

    encode_ts_ns = time.time_ns()
    # Root span for the whole volume encode: the pipeline (ec.encode)
    # nests under it along with index sort and sidecar publication.
    sp = trace.start(
        "ec.encode_volume", name=os.path.basename(base), base=base,
    )
    try:
        with trace.activate(sp):
            with trace.stage(sp, "index_sort"):
                write_sorted_file_from_idx(base)
            # Crash window the ecx-first ordering closes: .ecx exists,
            # no shards.
            faults.fire("ec.encode.after_ecx", base=base)
            prot = write_ec_files(
                base, ctx, backend, batch_size, leaf_size=leaf_size,
                scheduler=scheduler,
            )
            prot.generation = encode_ts_ns
            # Crash window: shards durable, sidecar absent — readers
            # must serve, scrub must refuse (no ground truth), rebuild
            # must still work.
            faults.fire("ec.encode.before_ecsum", base=base)
            with trace.stage(sp, "fsync_publish"):
                prot.save(base + ".ecsum")

                vi = VolumeInfo(
                    version=version,
                    ec_ctx=ctx,
                    dat_file_size=os.path.getsize(base + ".dat"),
                    encode_ts_ns=encode_ts_ns,
                )
                vi.save(base + ".vif")
            return vi
    finally:
        trace.finish(sp)
