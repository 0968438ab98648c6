"""Rebuild missing EC shards from surviving ones.

Reference: weed/storage/erasure_coding/ec_encoder.go generateMissingEcFiles
(:147-379). The correctness envelope preserved here (the reference's
accumulated bug-fix scar tissue, SURVEY.md hard part (c)):

- bitrot sidecar verify-and-exclude: present-but-corrupt shards are
  reclassified as missing and regenerated, never fed to Reed-Solomon;
- fail-closed rules: malformed sidecar refuses; >parity mismatches means
  the *sidecar* is suspect (wholesale-mismatch guard) and refuses;
  fewer than k verified-good shards refuses;
- regenerated shards are verified against the sidecar before publish;
- temp file + fsync + atomic rename (+ dir fsync) publication; corrupt
  originals replaced in place only after their replacement verifies.

Performance (PR 2): the rebuild runs the shared 3-stage pipeline
(ec/pipeline.py) — surviving-shard reads / Reed-Solomon apply / fused
native write+CRC — and the k SOURCE shards are sidecar-verified INLINE
by the read stage (CRC rolled while the batch is cache-hot), deleting
the separate whole-shard verification read pass the serial
implementation paid up front. Only the non-source remainder still gets
a dedicated verify, in parallel. A source whose inline CRC mismatches
is re-checked from disk: confirmed rot is reclassified corrupt and the
rebuild restarts without it (the verify-and-exclude envelope); a clean
disk copy means transient read corruption, which fails closed.
"""

from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import faults
from ..ops import gf256
from ..utils import trace
from ..utils.crc import crc32c
from .backend import RSBackend, _decode_coeffs, get_backend
from .bitrot import BitrotError, BitrotProtection
from .context import BITROT_BLOCK_SIZE, DEFAULT_EC_CONTEXT, ECContext, ECError
from .decoder import _fsync_dir
from .encoder import DEFAULT_BATCH, WIDE_STREAM_BYTES
from .pipeline import PyShardSink, make_shard_sink, run_pipeline, run_staged_apply
from .volume_info import VolumeInfo


class _SourceReadError(Exception):
    """A source shard failed mid-pipeline (unreadable/short read)."""

    def __init__(self, shards: list[int]):
        super().__init__(f"source shards {shards} unreadable")
        self.shards = shards


class _BlockCrcRoller:
    """Rolling per-block CRC32C over numpy rows, zero-copy (the inline
    source-verification half of the fused read stage)."""

    def __init__(self, block_size: int):
        self.block_size = block_size
        self.crcs: list[int] = []
        self._crc = 0
        self._filled = 0

    def update(self, arr: np.ndarray) -> None:
        pos, n = 0, len(arr)
        while pos < n:
            take = min(self.block_size - self._filled, n - pos)
            self._crc = crc32c(arr[pos : pos + take], self._crc)
            self._filled += take
            pos += take
            if self._filled == self.block_size:
                self.crcs.append(self._crc)
                self._crc = 0
                self._filled = 0

    def finish(self) -> list[int]:
        if self._filled:
            self.crcs.append(self._crc)
            self._crc = 0
            self._filled = 0
        return self.crcs


def _pread_exact(fd: int, buf: np.ndarray, offset: int) -> None:
    """Fill `buf` from fd at `offset` IN PLACE; short read raises."""
    mv = memoryview(buf)
    filled = 0
    want = len(buf)
    while filled < want:
        got = os.preadv(fd, [mv[filled:]], offset + filled)
        if got == 0:
            raise OSError(f"short shard read at offset {offset + filled}")
        filled += got


def rebuild_ec_files(
    base: str,
    ctx: ECContext | None = None,
    backend: RSBackend | None = None,
    unsafe_ignore_sidecar: bool = False,
    batch_size: int = DEFAULT_BATCH,
    only_shards: list[int] | None = None,
    priority: str = "recovery",
    scheduler=None,
) -> list[int]:
    """Regenerate missing/corrupt shard files; returns regenerated ids.

    `only_shards` restricts which ABSENT shards are regenerated (a
    subset-holding server must not mint local copies of shards placed on
    peers); present-but-corrupt shards are always replaced regardless.

    Each batch goes through the backend's staged apply (async H2D +
    device compute, D2H forced in the writer thread), so a device
    rebuild overlaps transfer with compute like `encode_staged`.

    `priority` tags the staged stream's class on the shared per-chip
    scheduler (ec/device_queue.py): "recovery" by default (rebuild and
    decode self-heal restore redundancy behind serving traffic); the
    scrub daemon passes "scrub" so background hygiene yields to both.

    `scheduler` is the QueueScope whose placement/admission config the
    staged stream runs under (None = the process-wide default scope);
    on a multi-chip backend the rebuild stream is placed whole onto the
    least-loaded chip (ec/chip_pool.py) instead of column-slicing
    across the pod.
    """
    # Sidecar first: it records the shard ratio too, which backs up the
    # .vif for config resolution and cross-checks it.
    prot: BitrotProtection | None = None
    ecsum = base + ".ecsum"
    if os.path.exists(ecsum):
        try:
            prot = BitrotProtection.load(ecsum)
        except BitrotError as e:
            if not unsafe_ignore_sidecar:
                raise ECError(
                    f"bitrot sidecar for {base} is malformed ({e}); refusing "
                    f"to rebuild (pass unsafe_ignore_sidecar to override)"
                ) from e
            prot = None

    if ctx is None:
        vif_path = base + ".vif"
        if os.path.exists(vif_path):
            # .vif present but unreadable fails closed: silently falling
            # back to 10+4 would rebuild a custom-ratio volume with the
            # wrong layout (reference RebuildEcFiles).
            vi = VolumeInfo.load(vif_path)
            ctx = vi.ec_ctx
        if ctx is None and prot is not None:
            ctx = prot.ctx
        if ctx is None:
            ctx = DEFAULT_EC_CONTEXT
    if prot is not None and prot.ctx != ctx:
        if not unsafe_ignore_sidecar:
            raise ECError(
                f"bitrot sidecar for {base} records ratio {prot.ctx} but the "
                f"volume config says {ctx}; refusing to rebuild"
            )
        prot = None
    # Backend resolution is DEFERRED until a reconstruction target
    # exists: the common no-op case (scrub of a healthy volume, decode's
    # verify pass with all shards present) is pure CPU CRC work and
    # must not open the device.

    total, k = ctx.total, ctx.data_shards
    present = [i for i in range(total) if os.path.exists(base + ctx.to_ext(i))]
    missing = [i for i in range(total) if i not in present]
    if only_shards is not None:
        missing = [i for i in missing if i in only_shards]

    # Flight-recorder root for the whole rebuild op (a child when a
    # decode/peer-rebuild/RPC span is active in this thread).
    sp = trace.start(
        "ec.rebuild", name=os.path.basename(base), base=base,
        present=len(present), missing=sorted(missing), priority=priority,
    )
    try:
        return _rebuild_ec_files_traced(
            base, ctx, backend, unsafe_ignore_sidecar, batch_size,
            prot, present, missing, priority, scheduler, sp,
        )
    finally:
        trace.finish(sp)


def _rebuild_ec_files_traced(
    base, ctx, backend, unsafe_ignore_sidecar, batch_size,
    prot, present, missing, priority, scheduler, sp,
) -> list[int]:
    total, k = ctx.total, ctx.data_shards

    # An armed fault registry routes through the PR1-faithful byte path:
    # mutating faults need materialized bytes at the read/write seams,
    # and the chaos contract (upfront verify of every present shard,
    # fail-closed on mid-rebuild read corruption) is asserted against
    # that shape. Disarmed — i.e. production — takes the fused path.
    chaos = faults.active()
    present0 = len(present)
    all_corrupt: list[int] = []
    verified_ok: set[int] = set()

    def _verify_full(ids: list[int]) -> list[int]:
        """Whole-shard sidecar verification (parallel across shards —
        each is an independent read+CRC stream, so N shards drain N
        queues instead of serializing)."""
        if prot is None or not ids:
            return []

        def check(i: int) -> bool:
            try:
                return bool(
                    prot.verify_shard_file(
                        base + ctx.to_ext(i), i, stop_early=True
                    )
                )
            except OSError:
                return True  # unreadable = untrustworthy RS input

        with trace.stage(sp, "verify"):
            if len(ids) == 1:
                flags = [check(ids[0])]
            else:
                with ThreadPoolExecutor(max_workers=min(len(ids), 8)) as ex:
                    flags = list(ex.map(check, ids))
        bad = [i for i, f in zip(ids, flags) if f]
        verified_ok.update(i for i in ids if i not in bad)
        return bad

    def _reclassify(new_bad: list[int]) -> None:
        """Corrupt bookkeeping + the PR1 fail-closed guards."""
        for i in new_bad:
            if i not in all_corrupt:
                all_corrupt.append(i)
        if unsafe_ignore_sidecar:
            return  # tolerate corrupt inputs, as the flag promises
        if len(all_corrupt) > ctx.parity_shards:
            raise ECError(
                f"bitrot sidecar suspect for {base}: {len(all_corrupt)}/"
                f"{present0} present shards mismatch (> parity "
                f"{ctx.parity_shards}); refusing to rebuild"
            )
        if present0 - len(all_corrupt) < k:
            raise ECError(
                f"bitrot: only {present0 - len(all_corrupt)} verified-good "
                f"shards for {base}, need {k} data shards"
            )
        for i in new_bad:
            if i in present:
                present.remove(i)
                missing.append(i)

    if prot is not None and chaos:
        # PR1 path: verify-and-exclude every present shard before any
        # reconstruction input is chosen.
        _reclassify(_verify_full(list(present)))

    while True:
        if len(present) < k:
            raise ECError(
                f"not enough shards to rebuild {base}: found {len(present)}, "
                f"need {k}, missing {sorted(missing)}"
            )
        if not missing:
            # Nothing absent — but a present shard may still be rotten
            # on disk (the verify-and-exclude contract repairs it in
            # place). With no reconstruction stream to fold the check
            # into, every still-unverified shard gets the dedicated
            # parallel verify.
            if prot is not None and not chaos and not unsafe_ignore_sidecar:
                bad = _verify_full(
                    [i for i in present if i not in verified_ok]
                )
                if bad:
                    _reclassify(bad)
                    continue
            return []

        sizes = {i: os.path.getsize(base + ctx.to_ext(i)) for i in present}
        if prot is not None and not chaos and not unsafe_ignore_sidecar:
            # size-vs-sidecar is the cheap half of verification
            # (truncation/growth is corruption) — catch it before the
            # stream even starts.
            size_bad = [
                i for i in present if sizes[i] != prot.shard_sizes[i]
            ]
            if size_bad:
                _reclassify(size_bad)
                continue
        shard_size = max(sizes.values())
        if [i for i, s in sizes.items() if s != shard_size]:
            raise ECError(f"present shards have unequal sizes: {sizes}")

        src = sorted(present)[:k]
        if prot is not None and not chaos and not unsafe_ignore_sidecar:
            # Non-source shards don't flow through the pipelined read,
            # so they get the dedicated (parallel) verify; sources are
            # verified inline below.
            bad = _verify_full(
                [i for i in present if i not in src and i not in verified_ok]
            )
            if bad:
                _reclassify(bad)
                continue

        targets = sorted(missing)
        if backend is None:
            backend = get_backend("auto", ctx.data_shards, ctx.parity_shards)
        bad_src = _attempt_rebuild(
            base, ctx, backend, prot, src, targets, shard_size,
            batch_size, chaos,
            inline_verify=(
                prot is not None and not chaos and not unsafe_ignore_sidecar
            ),
            verified_ok=verified_ok,
            priority=priority,
            scheduler=scheduler,
            span=sp,
        )
        if bad_src:
            # Confirmed on-disk rot in a source: verify-and-exclude says
            # reclassify it as missing and rebuild without it.
            _reclassify(bad_src)
            continue
        return targets


def _attempt_rebuild(
    base: str,
    ctx: ECContext,
    backend: RSBackend,
    prot: BitrotProtection | None,
    src: list[int],
    targets: list[int],
    shard_size: int,
    batch_size: int,
    chaos: bool,
    inline_verify: bool,
    verified_ok: set[int] | None = None,
    priority: str = "recovery",
    scheduler=None,
    span=None,
    src_base: str | None = None,
    remote=None,
) -> list[int]:
    """One pipelined reconstruction attempt. Publishes and returns []
    on success; returns confirmed-corrupt source ids for the caller to
    exclude and retry (inline-clean sources are recorded in
    `verified_ok` so a retry never re-reads them); raises fail-closed
    otherwise.

    `src_base` is where the local sources lie (default `base`, where the
    targets are written and published). `remote` streams the sources
    that are not on this disk (ec/peer_rebuild.py `PeerSources`): its
    `sids` are source ids, and `start(off, width, rows)` begins landing
    each one's batch in its row of the batch matrix and returns a
    landing whose `join()` waits for the rows and `check()` raises where
    one did not land verified. The local rows come first in the matrix,
    so one batched read fills them; no remote byte touches the disk."""
    k = ctx.data_shards
    src_base = base if src_base is None else src_base
    remote_ids = list(remote.sids) if remote is not None else []
    local = [i for i in src if i not in remote_ids]
    src = local + remote_ids  # the matrix's row order
    nl = len(local)
    fds = {i: os.open(src_base + ctx.to_ext(i), os.O_RDONLY) for i in local}
    tmp_paths = {i: base + ctx.to_ext(i) + ".rebuilding" for i in targets}
    # buffering=0: the fused native sink writes via raw fds; the Python
    # fallback writes whole >=1MiB batches where a userspace buffer
    # only adds a copy.
    outs = {i: open(p, "wb", buffering=0) for i, p in tmp_paths.items()}
    crc_block = prot.block_size if prot is not None else BITROT_BLOCK_SIZE
    # The fused native sink (sn_sink_append) rolls the sidecar-granularity
    # CRC while the reconstructed bytes are cache-hot and writes straight
    # from the backend's output buffers — no per-batch tobytes(). A
    # byte-mutating fault needs materialized bytes, so an armed registry
    # routes through the Python sink (the chaos tests' semantic path).
    sink = make_shard_sink(
        list(outs.values()), block_size=crc_block, prefer_fused=not chaos
    )
    use_bytes_path = isinstance(sink, PyShardSink)
    # Native read plane (ec/native_io.py): the k source rows land via
    # one batched pread per batch, and the inline source verification
    # CRC rolls on the C++ side in the same cache-hot pass — the Python
    # _BlockCrcRoller stays as the bit-identical fallback (and the
    # chaos path keeps its byte seams below).
    from . import native_io

    use_native = not chaos and native_io.enabled()
    rollers = None
    ncrc_state = ncrc_filled = None
    ncrc_lists: list[list[int]] | None = None
    if inline_verify:
        if use_native:
            ncrc_state = np.zeros(nl, np.uint32)
            ncrc_filled = np.zeros(nl, np.uint64)
            ncrc_lists = [[] for _ in range(nl)]
        else:
            rollers = {i: _BlockCrcRoller(crc_block) for i in local}

    # With remote sources the producer times its own parts: the local
    # read as `disk_read`, the wait for the peers' rows as the
    # landing's `source_land`.
    read_stage = "disk_read" if remote is None else None

    def local_read():
        if remote is None:
            return contextlib.nullcontext()
        return trace.stage(span, "disk_read")

    @contextlib.contextmanager
    def landing(off: int, width: int, rows: dict):
        """The remote rows of one batch land while the block is open
        (the local read), and have landed, verified, after it."""
        if remote is None:
            yield
            return
        land = remote.start(off, width, rows)
        try:
            yield
        finally:
            land.join()
        land.check()

    if chaos:
        # PR1-faithful byte path: per-shard pread -> fault mutate ->
        # dict reconstruct -> (mutate ->) write.
        def produce():
            for off in range(0, shard_size, batch_size):
                width = min(batch_size, shard_size - off)
                rows = {i: np.empty(width, np.uint8) for i in remote_ids}
                with landing(off, width, rows), local_read():
                    block = {
                        i: np.frombuffer(
                            faults.mutate(
                                "ec.rebuild.read_shard",
                                os.pread(fds[i], width, off),
                                base=src_base, shard=i, offset=off,
                            ),
                            dtype=np.uint8,
                        )
                        for i in local
                    }
                if any(len(b) != width for b in block.values()):
                    raise ECError(f"short shard read at offset {off}")
                block.update(rows)
                yield off, block

        def transform(item):
            off, block = item
            return off, backend.reconstruct(block, want=targets)

        def consume(item):
            off, rec = item
            rows: list = []
            for i in targets:
                row = np.ascontiguousarray(rec[i], dtype=np.uint8)
                if use_bytes_path:
                    rows.append(
                        faults.mutate(
                            "ec.rebuild.shard_bytes", row.tobytes(),
                            base=base, shard=i, offset=off,
                        )
                    )
                else:
                    rows.append(row)
            sink.append_rows(rows)

    else:
        # Fused path: read all k sources into one (k, width) matrix
        # (inline CRC rolled while cache-hot), then a single
        # precomputed-coefficient GF(256) apply per batch — no per-batch
        # matrix inversion, no stack copy, no dict plumbing. The apply
        # is dispatched through the backend's async hooks
        # (run_staged_apply), so on a device batch N computes while
        # N+1 uploads and N-1 drains to disk.
        rs = gf256.ReedSolomon(ctx.data_shards, ctx.parity_shards)
        coeffs = _decode_coeffs(rs.matrix, k, tuple(targets), tuple(src))

        pool = native_io.batch_pool() if use_native else None

        def produce():
            src_fds = [fds[i] for i in local]
            out_crcs = out_counts = None
            if ncrc_lists is not None:
                out_crcs = np.empty(
                    (nl, batch_size // crc_block + 2), np.uint32
                )
                out_counts = np.empty(nl, np.int32)
            for off in range(0, shard_size, batch_size):
                width = min(batch_size, shard_size - off)
                if use_native:
                    # a matrix that an earlier batch or operation of the
                    # process filled: its pages are mapped already
                    buf, held = pool.get(k, width)
                else:
                    buf, held = np.empty((k, width), dtype=np.uint8), False
                rows = {i: buf[nl + j] for j, i in enumerate(remote_ids)}
                with landing(off, width, rows), local_read():
                    trace.count("read_bytes", k * width)
                    if held:
                        trace.count("read_reused_bytes", k * width)
                    if use_native and nl:
                        nxt = off + width
                        if nxt < shard_size:
                            nw = min(batch_size, shard_size - nxt)
                            for fd in src_fds:
                                native_io.prefetch(fd, nxt, nw)
                        try:
                            native_io.read_batch(
                                src_fds, [off] * nl, buf[:nl], pad_eof=False,
                                granule=crc_block if ncrc_lists is not None else 0,
                                crc_state=ncrc_state, filled_state=ncrc_filled,
                                out_crcs=out_crcs, out_counts=out_counts,
                            )
                        except OSError as e:
                            raise _SourceReadError(
                                [local[getattr(e, "sn_row", 0)]]
                            ) from e
                        if ncrc_lists is not None:
                            for row in range(nl):
                                c = int(out_counts[row])
                                ncrc_lists[row].extend(
                                    int(x) for x in out_crcs[row, :c]
                                )
                    elif not use_native:
                        for row, i in enumerate(local):
                            try:
                                _pread_exact(fds[i], buf[row], off)
                            except OSError as e:
                                raise _SourceReadError([i]) from e
                            if rollers is not None:
                                rollers[i].update(buf[row])
                yield buf, buf

        def consume(buf, out):
            out = np.ascontiguousarray(out, dtype=np.uint8)
            sink.append_rows([out[p] for p in range(len(targets))])
            if pool is not None:
                # to_host has returned for this batch: nothing reads
                # its matrix any more, neither the upload nor a
                # failover's replay on the CPU
                pool.put(buf)

    def _cleanup_temps() -> None:
        for f in outs.values():
            f.close()
        for p in tmp_paths.values():
            if os.path.exists(p):
                os.unlink(p)

    def _confirm_from_disk(suspects: list[int]) -> list[int]:
        """Re-verify suspect sources from disk: confirmed rot is
        excludable; a clean disk copy means the PIPELINE's read was
        transiently corrupted and publishing anything would launder it."""
        confirmed, transient = [], []
        with trace.stage(span, "verify"):
            for i in suspects:
                try:
                    still_bad = bool(
                        prot.verify_shard_file(
                            src_base + ctx.to_ext(i), i, stop_early=True
                        )
                    )
                except OSError:
                    still_bad = True
                (confirmed if still_bad else transient).append(i)
        if transient:
            raise ECError(
                f"source shards {transient} for {base} failed read-time "
                f"sidecar verification but verify clean on disk (transient "
                f"read corruption); refusing to publish"
            )
        return confirmed

    try:
        # Shared 3-stage overlap (ec/pipeline.py): surviving-shard reads
        # / Reed-Solomon reconstruct / fused write+CRC of the
        # regenerated shards — batch N reconstructs while N+1 is read
        # and N-1 drains to disk, same shape as the encode path. The
        # staged fused path additionally overlaps H2D/compute/D2H inside
        # the reconstruct stage (device dispatch in the calling thread,
        # result forced in the writer thread).
        join_timeout = 60.0 + 4.0 * batch_size / (16 << 20)
        if chaos:
            run_pipeline(
                produce,
                transform,
                consume,
                join_timeout=join_timeout,
                describe="ec rebuild pipeline",
                span=span,
                stage_names=(read_stage, "reconstruct", "write_sink"),
            )
        else:
            run_staged_apply(
                backend,
                coeffs,
                produce,
                consume,
                join_timeout=join_timeout,
                describe="ec rebuild pipeline",
                priority=priority,
                scheduler=scheduler,
                span=span,
                # total stream cost for least-loaded routing: every
                # target row spans the whole shard extent
                cost_hint=len(targets) * shard_size,
                # a lone huge rebuild on an idle pod keeps the mesh
                # like a wide encode does — pinning it to one chip
                # would multiply MTTR exactly while redundancy is
                # reduced; same source-bytes threshold as encode
                wide=k * shard_size >= WIDE_STREAM_BYTES,
                read_stage=read_stage,
            )
    except _SourceReadError as e:
        _cleanup_temps()
        if inline_verify:
            return e.shards  # unreadable = untrustworthy; exclude + retry
        # No exclusion machinery active (no sidecar, or
        # unsafe_ignore_sidecar): the caller's _reclassify would not
        # remove the shard and the identical attempt would spin forever
        # — propagate instead, like the serial implementation did.
        raise ECError(str(e)) from e
    except BaseException:
        _cleanup_temps()
        raise
    finally:
        for fd in fds.values():
            os.close(fd)

    # --- inline source verification verdict (fast path) -------------------
    if rollers is not None or ncrc_lists is not None:
        if ncrc_lists is not None:
            # flush partial-tail CRC state (the native roller's finish)
            for row in range(nl):
                if ncrc_filled[row]:
                    ncrc_lists[row].append(int(ncrc_state[row]))
                    ncrc_filled[row] = 0
            got = {i: ncrc_lists[row] for row, i in enumerate(local)}
        else:
            got = {i: rollers[i].finish() for i in local}
        # remote rows were verified granule by granule as they landed
        suspects = [i for i in local if got[i] != prot.shard_crcs[i]]
        if verified_ok is not None:
            # the inline roller IS the block-CRC check _verify_full
            # performs — a retry after an exclusion must not re-read
            # the sources that just verified clean
            verified_ok.update(i for i in local if i not in suspects)
        if suspects:
            _cleanup_temps()
            return _confirm_from_disk(suspects)

    try:
        # Crash window: temp .rebuilding files written, not yet durable.
        faults.fire("ec.rebuild.before_fsync", base=base)
        with trace.stage(span, "fsync_publish"):
            for f in outs.values():
                f.flush()
                os.fsync(f.fileno())
    except BaseException:
        _cleanup_temps()
        raise

    for f in outs.values():
        f.close()

    # --- verify regenerated shards against the sidecar (fail closed) -----
    if prot is not None:
        out_sizes = sink.sizes
        out_crcs = sink.block_crcs()
        for pos, i in enumerate(targets):
            if (
                out_sizes[pos] != prot.shard_sizes[i]
                or out_crcs[pos] != prot.shard_crcs[i]
            ):
                for p in tmp_paths.values():
                    if os.path.exists(p):
                        os.unlink(p)
                raise ECError(
                    f"regenerated shard {i} for {base} fails sidecar "
                    f"verification; refusing to publish"
                )

    # Crash window: temps durable + sidecar-verified, renames pending. A
    # crash here (or between renames) leaves a mix of published shards
    # and .rebuilding temps; a restarted rebuild regenerates the rest.
    faults.fire("ec.rebuild.before_rename", base=base)
    with trace.stage(span, "fsync_publish"):
        for i in targets:
            os.replace(tmp_paths[i], base + ctx.to_ext(i))
            faults.fire("ec.rebuild.after_rename", base=base, shard=i)
        _fsync_dir(base + ".dat")
    return []
