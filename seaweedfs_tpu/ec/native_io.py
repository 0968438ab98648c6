"""Zero-copy native read plane for the EC byte path (ISSUE 10).

The Python layer ORCHESTRATES buffers here instead of copying them:
batches land via one GIL-releasing `sn_batch_pread` call per batch into
caller-owned aligned numpy matrices that flow produce -> transform ->
consume untouched (numpy views over one allocation — no `bytes`
objects, no per-batch malloc/page-fault churn), then return to one
process-wide bounded pool (`batch_pool()`). The write half is the
stateful native sink (utils/native.py NativeSink, used by
pipeline.FusedShardSink). The NETWORK half lives in ec/net_plane.py
(ISSUE 12): `landing_pool()` backs the peer-fetch ingress landings and
the fastread client, and `enabled()` below is the single gate every
plane (local, wire, HTTP egress) checks.

Buffer-ownership rules (README "Native data plane" has the long form):

- A pooled matrix belongs to exactly one in-flight batch from the
  moment a pool's `get` returns it until the consume stage hands it
  back with `put`: for a staged batch (`batch_pool()`, encode and
  rebuild) that is after `backend.to_host` has returned for the batch
  and its rows are with the sink. Until then the runtime may still be
  reading the matrix for the upload, and `FallbackBackend` carries it
  as the host copy a mid-batch failover replays on the CPU. `get`
  never blocks: it allocates when the pool holds no matrix of the
  shape.
- Rows handed to the native sink must stay alive until the append call
  returns (the C side pwrite(2)s straight from them; it stores no
  pointers).
- Pool matrices are 4096-aligned so the same buffers satisfy O_DIRECT
  alignment when a caller opens shard fds with it (offsets and widths
  must then also be 512/4096-multiples; the ragged tail batch is not,
  which is why O_DIRECT stays an opt-in for aligned workloads).

Fallback semantics: `enabled()` is False when the native core failed to
import (no C++ toolchain — utils/native.py raises ImportError by
contract) or when SEAWEED_EC_NATIVE=0 forces the pure-Python plane;
callers must keep their Python source/sink paths as the bit-identical
fallback. An ARMED fault registry also routes callers to the Python
plane: byte-mutating fault points need materialized bytes at the
read/write seams (see ec/rebuild.py).
"""

from __future__ import annotations

import os
import threading
from typing import Sequence

import numpy as np

_ALIGN = 4096


def _native_mod():
    try:
        from ..utils import native

        return native
    except ImportError:
        return None


def enabled() -> bool:
    """True when the native data plane should carry reads/writes:
    the .so loaded and SEAWEED_EC_NATIVE != 0 (checked live so tests
    can flip the env per call)."""
    if os.environ.get("SEAWEED_EC_NATIVE", "1") == "0":
        return False
    return _native_mod() is not None


def aligned_matrix(rows: int, width: int, align: int = _ALIGN) -> np.ndarray:
    """(rows, width) C-contiguous uint8 matrix whose base address is
    `align`-aligned (over-allocate + offset; plain numpy, no custom
    allocator to keep GC ownership trivial)."""
    raw = np.empty(rows * width + align, dtype=np.uint8)
    off = (-raw.ctypes.data) % align
    return raw[off : off + rows * width].reshape(rows, width)


_pool_lock = threading.Lock()
_landing_pool_singleton = None
_batch_pool_singleton = None


def landing_pool() -> "BufferPool":
    """Process-wide width-keyed pool of 1-row aligned landing buffers,
    shared by every single-stream ingress (peer-fetch net-plane
    landings, the fastread client) so steady state allocates once per
    width and reuses forever."""
    global _landing_pool_singleton
    with _pool_lock:
        if _landing_pool_singleton is None:
            _landing_pool_singleton = BufferPool(rows=1)
        return _landing_pool_singleton


def batch_pool() -> "BatchPool":
    """Process-wide pool of the (rows, width) matrices that a
    pipeline's reader fills (ec/encoder.py, ec/rebuild.py): from the
    second batch in flight on, and in every later operation of the
    process, a batch lands in pages that are already committed and
    mapped, and no 160 MiB mapping is made or torn down on a pipeline
    thread. It keeps what one pipeline can have alive."""
    global _batch_pool_singleton
    with _pool_lock:
        if _batch_pool_singleton is None:
            from .pipeline import BATCHES_ALIVE

            _batch_pool_singleton = BatchPool(keep=BATCHES_ALIVE)
        return _batch_pool_singleton


class BufferPool:
    """Reusable aligned (rows, width) matrices free-listed by exact
    width, for the one-row landings of `landing_pool()`. Allocation
    happens on demand; release is cooperative (`put` when the bytes
    have been consumed), and a buffer that is never put back is the
    collector's: the pool holds no list of what it handed out."""

    def __init__(self, rows: int):
        self.rows = rows
        self._free: dict[int, list[np.ndarray]] = {}
        self._lock = threading.Lock()

    def get(self, width: int) -> np.ndarray:
        with self._lock:
            lst = self._free.get(width)
            if lst:
                return lst.pop()
        return aligned_matrix(self.rows, width)

    def put(self, buf: np.ndarray) -> None:
        with self._lock:
            self._free.setdefault(buf.shape[1], []).append(buf)


class BatchPool:
    """At most `keep` free staged-batch matrices of any shape, handed
    out by exact (rows, width): an encode plan's full batches, its
    small-block phase and a ragged tail are classes of their own, as
    are 10+4, 4+2 and 16+4 (a column slice of a wider matrix would be
    copied on its way to the device). `get` says whether the matrix
    had been held, and allocates when none of the shape is; a `put`
    over the bound drops the matrix that has lain longest, so the
    shapes of operations long past make room for those of the running
    one and the resident memory stays `keep` matrices of the widest
    class. A batch that an aborting pipeline drops strands its matrix
    for the collector: the pool holds no list of what it handed out."""

    def __init__(self, keep: int):
        self.keep = keep
        self._free: list[np.ndarray] = []  # longest-lying first
        self._lock = threading.Lock()

    def get(self, rows: int, width: int) -> tuple[np.ndarray, bool]:
        with self._lock:
            for i in range(len(self._free) - 1, -1, -1):
                if self._free[i].shape == (rows, width):
                    return self._free.pop(i), True
        return aligned_matrix(rows, width), False

    def put(self, buf: np.ndarray) -> None:
        with self._lock:
            self._free.append(buf)
            # held past the lock: a dropped matrix is unmapped outside it
            dropped = self._free.pop(0) if len(self._free) > self.keep else None
        del dropped


def read_batch(
    fds: Sequence[int],
    offsets: Sequence[int],
    dst: np.ndarray,
    *,
    width: int | None = None,
    pad_eof: bool = True,
    granule: int = 0,
    crc_state: np.ndarray | None = None,
    filled_state: np.ndarray | None = None,
    out_crcs: np.ndarray | None = None,
    out_counts: np.ndarray | None = None,
) -> None:
    """One native batched positioned read into `dst` rows (see
    utils/native.batch_pread for the contract). Caller must have
    checked `enabled()`."""
    native = _native_mod()
    native.batch_pread(
        list(fds),
        list(offsets),
        dst,
        width=width,
        pad_eof=pad_eof,
        granule=granule,
        crc_state=crc_state,
        filled_state=filled_state,
        out_crcs=out_crcs,
        out_counts=out_counts,
    )


def read_exact_into(fd: int, buf: np.ndarray, offset: int) -> None:
    """Fill 1-D `buf` from fd at offset; short read raises. Native
    single-row read when available, preadv loop otherwise — same
    in-place no-bytes contract either way."""
    if enabled():
        read_batch([fd], [offset], buf.reshape(1, -1), pad_eof=False)
        return
    mv = memoryview(buf)
    filled = 0
    want = len(buf)
    while filled < want:
        got = os.preadv(fd, [mv[filled:]], offset + filled)
        if got == 0:
            raise OSError(f"short read at offset {offset + filled}")
        filled += got


def prefetch(fd: int, offset: int, length: int) -> None:
    """Best-effort readahead for the NEXT batch window: issued before
    reading the current batch so the kernel pages in batch N+1 while
    batch N computes and N-1 drains."""
    native = _native_mod()
    if native is not None and length > 0:
        native.fadvise_willneed(fd, offset, length)
