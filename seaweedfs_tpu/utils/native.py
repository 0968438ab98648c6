"""ctypes loader for the C++ native core (native/libseaweed_native.so).

Builds on first use if the shared object is missing (make in native/).
All callers must tolerate ImportError and fall back to pure Python —
the native core is an accelerator, not a dependency.
"""

from __future__ import annotations

import ctypes
import glob as _glob
import os
import subprocess

import numpy as np

from . import trace as _trace

_NATIVE_DIR = os.environ.get(
    "SEAWEED_NATIVE_DIR",
    os.path.join(
        os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        ),
        "native",
    ),
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libseaweed_native.so")


def _build() -> None:
    subprocess.run(
        ["make", "-s", "-C", _NATIVE_DIR], check=True, capture_output=True
    )


def _stale() -> bool:
    """Rebuild when sources are newer than the .so — a stale library
    missing newly-added symbols would otherwise fail the whole module
    import and silently disable ALL native acceleration. The source set
    is derived from the directory (every .cpp/.h plus the Makefile), not
    a hardcoded list, so adding a source file triggers rebuilds too."""
    if not os.path.exists(_SO_PATH):
        return True
    so_mtime = os.path.getmtime(_SO_PATH)
    sources = [os.path.join(_NATIVE_DIR, "Makefile")]
    for pat in ("*.cpp", "*.cc", "*.h", "*.hpp"):
        sources.extend(_glob.glob(os.path.join(_NATIVE_DIR, pat)))
    for p in sources:
        if os.path.exists(p) and os.path.getmtime(p) > so_mtime:
            return True
    return False


# Load contract: every caller is documented to tolerate ImportError and
# fall back to pure Python. A missing C++ toolchain surfaces as
# subprocess.CalledProcessError from make, a bad .so as OSError from
# CDLL — both would otherwise escape import and crash callers that
# correctly guard with `except ImportError`. Wrap them so the fallback
# actually engages; the original failure rides along as __cause__.
try:
    if _stale():
        _build()
    _lib = ctypes.CDLL(_SO_PATH)
except (OSError, subprocess.CalledProcessError) as e:
    detail = e
    if isinstance(e, subprocess.CalledProcessError) and e.stderr:
        detail = e.stderr.decode(errors="replace")[-500:]
    raise ImportError(
        f"native core unavailable (build or load of {_SO_PATH} failed): "
        f"{detail}"
    ) from e

_lib.sn_crc32c.restype = ctypes.c_uint32
_lib.sn_crc32c.argtypes = [ctypes.c_uint32, ctypes.c_void_p, ctypes.c_size_t]
_lib.sn_rs_apply.restype = None
_lib.sn_rs_apply.argtypes = [
    ctypes.c_char_p,
    ctypes.c_int,
    ctypes.c_int,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_size_t,
]
_lib.sn_gf_mul.restype = ctypes.c_uint8
_lib.sn_gf_mul.argtypes = [ctypes.c_uint8, ctypes.c_uint8]
_lib.sn_rs_apply_mt.restype = None
_lib.sn_rs_apply_mt.argtypes = [
    ctypes.c_char_p,
    ctypes.c_int,
    ctypes.c_int,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_size_t,
    ctypes.c_int,
]
_lib.sn_shard_append.restype = ctypes.c_int
_lib.sn_shard_append.argtypes = [
    ctypes.POINTER(ctypes.c_int),
    ctypes.POINTER(ctypes.c_void_p),
    ctypes.c_int,
    ctypes.c_size_t,
    ctypes.c_uint32,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_int32,
]
_lib.sn_batch_pread.restype = ctypes.c_int
_lib.sn_batch_pread.argtypes = [
    ctypes.POINTER(ctypes.c_int),     # fds
    ctypes.POINTER(ctypes.c_uint64),  # offsets
    ctypes.c_int,                     # nrows
    ctypes.c_void_p,                  # dst
    ctypes.c_size_t,                  # width
    ctypes.c_size_t,                  # stride
    ctypes.c_int,                     # pad_eof
    ctypes.c_uint32,                  # granule
    ctypes.c_void_p,                  # crc_state
    ctypes.c_void_p,                  # filled_state
    ctypes.c_void_p,                  # out_crcs
    ctypes.c_void_p,                  # out_counts
    ctypes.c_int32,                   # max_out
    ctypes.c_void_p,                  # ret_ns (i64[1] or NULL: see _stamped)
]
_lib.sn_crc32c_granules.restype = None
_lib.sn_crc32c_granules.argtypes = [
    ctypes.c_void_p,   # rows
    ctypes.c_int,      # nrows
    ctypes.c_size_t,   # width
    ctypes.c_size_t,   # stride
    ctypes.c_uint32,   # granule
    ctypes.c_void_p,   # out (u32[nrows * ceil(width / granule)])
    ctypes.c_void_p,   # ret_ns
]
_lib.sn_fadvise_willneed.restype = ctypes.c_int
_lib.sn_fadvise_willneed.argtypes = [
    ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
]
_lib.sn_crc32c_combine.restype = ctypes.c_uint32
_lib.sn_crc32c_combine.argtypes = [
    ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
]
_lib.sn_sink_create.restype = ctypes.c_void_p
_lib.sn_sink_create.argtypes = [
    ctypes.POINTER(ctypes.c_int),
    ctypes.c_int,
    ctypes.c_uint32,
    ctypes.c_uint32,
    ctypes.c_uint32,
]
_lib.sn_sink_append.restype = ctypes.c_int
_lib.sn_sink_append.argtypes = [
    ctypes.c_void_p,                   # handle
    ctypes.POINTER(ctypes.c_void_p),   # rows
    ctypes.c_size_t,                   # width
    ctypes.c_void_p,                   # out_block_crcs
    ctypes.c_void_p,                   # out_block_counts
    ctypes.c_void_p,                   # out_leaf_crcs
    ctypes.c_void_p,                   # out_leaf_counts
    ctypes.c_int32,                    # max_out
    ctypes.c_void_p,                   # ret_ns
]
_lib.sn_sink_finish.restype = ctypes.c_int
_lib.sn_sink_finish.argtypes = [
    ctypes.c_void_p,
    ctypes.c_void_p,  # tail_block_crc (u32[n])
    ctypes.c_void_p,  # tail_block_valid (u8[n])
    ctypes.c_void_p,  # tail_leaf_crc (u32[n])
    ctypes.c_void_p,  # tail_leaf_valid (u8[n])
    ctypes.c_void_p,  # sizes (u64[n])
]
_lib.sn_sink_destroy.restype = None
_lib.sn_sink_destroy.argtypes = [ctypes.c_void_p]
# Network byte plane (ISSUE 12): socket egress/ingress with the GIL
# released for the whole transfer. A stale .so missing these symbols
# fails HERE at import (AttributeError -> ImportError below would not
# catch it, which is deliberate: _stale() rebuilds first, and the
# tier-1 symbol gate in tests/test_native_plane.py asserts the ABI).
_lib.sn_send_file.restype = ctypes.c_int64
_lib.sn_send_file.argtypes = [
    ctypes.c_int,     # out_fd (socket)
    ctypes.c_int,     # in_fd (file)
    ctypes.c_uint64,  # offset
    ctypes.c_uint64,  # len
    ctypes.c_int,     # timeout_ms (-1 = block)
    ctypes.c_void_p,  # ret_ns
]
_lib.sn_sendv.restype = ctypes.c_int64
_lib.sn_sendv.argtypes = [
    ctypes.c_int,
    ctypes.POINTER(ctypes.c_void_p),  # bufs
    ctypes.POINTER(ctypes.c_uint64),  # lens
    ctypes.c_int,                     # n
    ctypes.c_int,                     # timeout_ms
    ctypes.c_void_p,                  # ret_ns
]
_lib.sn_recv_into.restype = ctypes.c_int64
_lib.sn_recv_into.argtypes = [
    ctypes.c_int,     # fd
    ctypes.c_void_p,  # dst
    ctypes.c_uint64,  # len
    ctypes.c_int,     # timeout_ms
    ctypes.c_uint32,  # granule
    ctypes.c_void_p,  # crc_state (u32[1])
    ctypes.c_void_p,  # filled_state (u64[1])
    ctypes.c_void_p,  # out_crcs (u32[max_out])
    ctypes.c_void_p,  # out_count (i32[1])
    ctypes.c_int32,   # max_out
    ctypes.c_int32,   # overlap_mode (0 serial / 1 overlap / -1 auto)
    ctypes.c_void_p,  # ret_ns
]
_lib.sn_recv_overlap_active.restype = ctypes.c_int
_lib.sn_recv_overlap_active.argtypes = [ctypes.c_uint64]
# Write-opcode blob landing (ISSUE 18): socket -> disk with the CRC
# fused into the bounce-buffer loop. Guarded so a prebuilt .so from an
# older tree (no toolchain to rebuild) degrades to the Python landing
# instead of failing the whole module import.
try:
    _lib.sn_recv_file.restype = ctypes.c_int64
    _lib.sn_recv_file.argtypes = [
        ctypes.c_int,     # fd (socket)
        ctypes.c_int,     # out_fd (file)
        ctypes.c_uint64,  # offset
        ctypes.c_uint64,  # len
        ctypes.c_int,     # timeout_ms
        ctypes.c_void_p,  # crc_out (u32[1])
    ]
    _HAS_RECV_FILE = True
except AttributeError:  # pragma: no cover - stale prebuilt .so
    _HAS_RECV_FILE = False
_lib.sn_sink_direct_flags.restype = ctypes.c_int
_lib.sn_sink_direct_flags.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
_lib.sn_has_avx2.restype = ctypes.c_int
_lib.sn_scan_dat.restype = ctypes.c_int64
_lib.sn_scan_dat.argtypes = [
    ctypes.c_char_p,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_void_p,
    ctypes.c_int64,
]
# the holder's side of a shard plane read in one call (ec/net_plane.py)
_lib.sn_plane_table_new.restype = ctypes.c_void_p
_lib.sn_plane_table_new.argtypes = []
_lib.sn_plane_table_put.restype = ctypes.c_int
_lib.sn_plane_table_put.argtypes = [
    ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
    ctypes.c_int,
]
_lib.sn_plane_table_drop.restype = None
_lib.sn_plane_table_drop.argtypes = [
    ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32,
]
_lib.sn_plane_table_clear.restype = None
_lib.sn_plane_table_clear.argtypes = [ctypes.c_void_p]
_lib.sn_plane_set_admissible.restype = None
_lib.sn_plane_set_admissible.argtypes = [ctypes.c_int]
_lib.sn_plane_serve.restype = ctypes.c_int
_lib.sn_plane_serve.argtypes = [
    ctypes.c_void_p,  # table
    ctypes.c_int,     # conn (socket)
    ctypes.c_int,     # timeout_ms
    ctypes.c_int,     # native_ok
    ctypes.c_void_p,  # rec (u64[PLANE_REC_SLOTS])
    ctypes.c_void_p,  # meta (u8[PLANE_META_MAX])
]
# the reader's side of shard plane reads in one call (ec/net_plane.py)
_lib.sn_plane_fetch.restype = ctypes.c_int
_lib.sn_plane_fetch.argtypes = [
    ctypes.c_int,                     # n ranges
    ctypes.POINTER(ctypes.c_int),     # fds
    ctypes.POINTER(ctypes.c_void_p),  # reqs
    ctypes.POINTER(ctypes.c_uint32),  # req_lens
    ctypes.POINTER(ctypes.c_uint64),  # sizes
    ctypes.c_int,                     # n_dsts
    ctypes.POINTER(ctypes.c_void_p),  # dsts
    ctypes.POINTER(ctypes.c_uint64),  # caps
    ctypes.c_int,                     # need
    ctypes.c_uint32,                  # granule
    ctypes.c_void_p,                  # out_crcs (u32[n, max_out])
    ctypes.c_int32,                   # max_out
    ctypes.c_void_p,                  # msgs (u8[n, msg_cap])
    ctypes.c_uint32,                  # msg_cap
    ctypes.c_int,                     # timeout_ms
    ctypes.c_int32,                   # overlap_mode
    ctypes.c_void_p,                  # rec (i64[n, len(FETCH_REC)])
    ctypes.c_void_p,                  # ret_ns
]
# the core-wait probe (utils/interp_probe.py owns its life)
_lib.sn_probe_start.restype = ctypes.c_int
_lib.sn_probe_start.argtypes = [ctypes.c_int64]
_lib.sn_probe_stop.restype = ctypes.c_int
_lib.sn_probe_stop.argtypes = []
_lib.sn_probe_head.restype = ctypes.c_uint64
_lib.sn_probe_head.argtypes = []
_lib.sn_probe_read.restype = ctypes.c_int32
_lib.sn_probe_read.argtypes = [
    ctypes.POINTER(ctypes.c_uint64),  # cursor, moved past what was read
    ctypes.c_void_p,                  # out (i64[max_pairs, 2]: wake, wait)
    ctypes.c_int32,                   # max_pairs
]


# The calls that do real work without the interpreter (batch_pread,
# crc32c_granules, sendv, NativeSink.append, and those that carry every
# byte of a read from a peer: send_file at the holder, plane_fetch and
# recv_into at the reader) end by writing
# CLOCK_MONOTONIC where their last argument, `ret_ns`, points: how long
# the thread then waits to hold the interpreter again is
# `perf_counter_ns()` on return less that stamp. Armed, the call gets a
# word and the difference goes to its span (trace.book_return);
# disarmed it gets NULL and the C side writes nothing: one module-bool
# check at each seam.


def _stamped(fn, *args):
    """fn(*args, ret_ns): the native call, its return booked if armed."""
    if not _trace.armed:
        return fn(*args, None)
    word = ctypes.c_int64(0)
    rc = fn(*args, ctypes.addressof(word))
    _trace.book_return(word.value)
    return rc


def crc32c(data, crc: int = 0) -> int:
    """Zero-copy over bytes/ndarray/memoryview/bytearray (buffer protocol)."""
    if isinstance(data, bytes):
        return _lib.sn_crc32c(crc, data, len(data))
    if not isinstance(data, np.ndarray):
        data = np.frombuffer(data, dtype=np.uint8)  # zero-copy view
    data = np.ascontiguousarray(data)
    return _lib.sn_crc32c(
        crc, ctypes.c_void_p(data.ctypes.data), data.nbytes
    )


def crc32c_granules(rows: np.ndarray, granule: int) -> np.ndarray:
    """CRC32C of every `granule`-byte piece of each row of a 2-D uint8
    matrix (rows contiguous, any row stride; the last piece of a row may
    be short) -> u32[nrows, ceil(width / granule)]. One GIL-releasing
    call, however many rows and pieces."""
    if rows.dtype != np.uint8 or rows.ndim != 2 or rows.strides[1] != 1:
        raise ValueError("crc32c_granules wants a 2-D uint8 matrix of contiguous rows")
    if granule <= 0:
        raise ValueError(f"granule {granule} must be positive")
    n, width = rows.shape
    out = np.empty((n, -(-width // granule)), dtype=np.uint32)
    if out.size:
        _stamped(
            _lib.sn_crc32c_granules,
            ctypes.c_void_p(rows.ctypes.data), n, width, rows.strides[0],
            granule, ctypes.c_void_p(out.ctypes.data),
        )
    return out


def rs_apply(coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[r] = XOR_j gf_mul(coeffs[r,j], data[j]) over contiguous rows."""
    coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    out_rows, in_rows = coeffs.shape
    if data.shape[0] != in_rows:
        raise ValueError(f"coeffs expect {in_rows} rows, got {data.shape[0]}")
    n = data.shape[1]
    out = np.empty((out_rows, n), dtype=np.uint8)
    _lib.sn_rs_apply(
        coeffs.tobytes(),
        out_rows,
        in_rows,
        data.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        n,
    )
    return out


def rs_apply_mt(coeffs: np.ndarray, data: np.ndarray, threads: int = 0) -> np.ndarray:
    """rs_apply with columns split across `threads` workers (0 = all cores).
    Bit-exact vs rs_apply: parity is columnwise-independent."""
    coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    out_rows, in_rows = coeffs.shape
    if data.shape[0] != in_rows:
        raise ValueError(f"coeffs expect {in_rows} rows, got {data.shape[0]}")
    if threads <= 0:
        threads = os.cpu_count() or 1
    n = data.shape[1]
    out = np.empty((out_rows, n), dtype=np.uint8)
    _lib.sn_rs_apply_mt(
        coeffs.tobytes(),
        out_rows,
        in_rows,
        data.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        n,
        threads,
    )
    return out


def shard_append(
    fds: list[int],
    row_ptrs: list[int],
    width: int,
    block_size: int,
    crc_state: np.ndarray,
    filled_state: np.ndarray,
    out_crcs: np.ndarray,
    out_counts: np.ndarray,
) -> None:
    """Fused batch append: write `width` bytes from row_ptrs[i] to fds[i]
    and roll shard i's block-CRC32C state — one GIL-releasing call per
    batch, a worker thread per shard, no Python-side copies.

    crc_state (u32[n]) / filled_state (u64[n]) carry across calls;
    completed block CRCs land in out_crcs (u32[n, max_out]) with counts
    in out_counts (i32[n]). Raises OSError on any shard write failure.
    """
    n = len(fds)
    assert len(row_ptrs) == n
    assert crc_state.dtype == np.uint32 and filled_state.dtype == np.uint64
    assert out_crcs.dtype == np.uint32 and out_crcs.flags.c_contiguous
    assert out_counts.dtype == np.int32
    rc = _lib.sn_shard_append(
        (ctypes.c_int * n)(*fds),
        (ctypes.c_void_p * n)(*row_ptrs),
        n,
        width,
        block_size,
        ctypes.c_void_p(crc_state.ctypes.data),
        ctypes.c_void_p(filled_state.ctypes.data),
        ctypes.c_void_p(out_crcs.ctypes.data),
        ctypes.c_void_p(out_counts.ctypes.data),
        out_crcs.shape[1],
    )
    if rc != 0:
        raise OSError(f"sn_shard_append failed on shard {-rc - 1}")


def batch_pread(
    fds: list[int],
    offsets: list[int],
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
    """Fill row i of `dst` (2-D C-contiguous uint8, or 1-D for n=1) with
    `width` bytes read from fds[i] at offsets[i] — one GIL-releasing
    call, a worker thread per row, no intermediate bytes objects.

    `dst` is CALLER-OWNED: rows land in place (the buffer-protocol /
    numpy-view contract of the zero-copy plane). `width` defaults to the
    full row; a narrower width fills a left-aligned slice of each row
    (the pool-backed ragged tail), leaving the remainder untouched.
    pad_eof zero-fills past EOF (encode semantics); pad_eof=False raises
    OSError on any short row (rebuild semantics).

    With granule > 0, each row's rolling CRC32C state
    (crc_state u32[n] / filled_state u64[n], persisting across calls)
    advances over the bytes read, completed granule CRCs landing in
    out_crcs (u32[n, max_out]) with counts in out_counts (i32[n]) — the
    fused read+verify used by the rebuild source path.
    """
    n = len(fds)
    assert len(offsets) == n
    if dst.ndim == 1:
        dst = dst.reshape(1, -1)
    assert dst.dtype == np.uint8 and dst.flags.c_contiguous
    assert dst.shape[0] == n
    stride = dst.shape[1]
    if width is None:
        width = stride
    assert 0 < width <= stride
    max_out = 0
    if granule:
        assert crc_state is not None and filled_state is not None
        assert out_crcs is not None and out_counts is not None
        assert crc_state.dtype == np.uint32
        assert filled_state.dtype == np.uint64
        assert out_crcs.dtype == np.uint32 and out_crcs.flags.c_contiguous
        assert out_counts.dtype == np.int32
        max_out = out_crcs.shape[1]
    rc = _stamped(
        _lib.sn_batch_pread,
        (ctypes.c_int * n)(*fds),
        (ctypes.c_uint64 * n)(*offsets),
        n,
        ctypes.c_void_p(dst.ctypes.data),
        width,
        stride,
        1 if pad_eof else 0,
        granule,
        ctypes.c_void_p(crc_state.ctypes.data) if granule else None,
        ctypes.c_void_p(filled_state.ctypes.data) if granule else None,
        ctypes.c_void_p(out_crcs.ctypes.data) if granule else None,
        ctypes.c_void_p(out_counts.ctypes.data) if granule else None,
        max_out,
    )
    if rc != 0:
        err = OSError(
            f"sn_batch_pread failed on row {-rc - 1} "
            f"(fd {fds[-rc - 1]} offset {offsets[-rc - 1]})"
        )
        err.sn_row = -rc - 1  # callers map the row back to a shard id
        raise err


def fadvise_willneed(fd: int, offset: int, length: int) -> None:
    """Best-effort readahead hint (errors ignored — a filesystem that
    rejects the advice just loses the prefetch)."""
    try:
        _lib.sn_fadvise_willneed(fd, offset, length)
    except Exception:  # pragma: no cover - defensive
        pass


# ---------------------------------------------------------------- network
# Socket egress/ingress (ISSUE 12). All three release the GIL for the
# whole transfer; `timeout_ms` bounds each poll() wait on a
# Python-timeout (O_NONBLOCK) socket, -1 blocks forever.


def send_file(
    out_fd: int, in_fd: int, offset: int, length: int, timeout_ms: int = -1
) -> int:
    """sendfile(2) `length` bytes of in_fd@offset into out_fd — kernel
    to kernel, zero userspace copies (one, via the C-side fallback
    buffer, where the kernel path is unsupported). Returns bytes sent;
    SHORT only when in_fd hits EOF. Raises OSError on socket errors or
    timeout."""
    sent = _stamped(
        _lib.sn_send_file, out_fd, in_fd, offset, length, timeout_ms
    )
    if sent < 0:
        raise OSError(-sent, f"sn_send_file: {os.strerror(-sent)}")
    return int(sent)


# sn_plane_serve: what it did (its return value and rec[0]) ...
PLANE_SERVED, PLANE_HANDED_BACK, PLANE_IDLE, PLANE_CLOSED, PLANE_NOT_PROTOCOL = range(5)
# ... and the slots of its record, as the C side numbers them
PLANE_REC = (
    "what", "magic", "vid", "sid", "gen", "off", "size", "mlen", "n",
    "sent", "ok", "t_req", "t_res", "t_hdr", "t_sent", "c_req", "c_res",
    "c_hdr", "c_sent",
)
PLANE_META_MAX = 4096


class PlaneTable:
    """A shard plane's native table: (volume, shard) -> a duplicate of
    the shard fd and the volume's generation, each under a reference
    count, so that a connection thread serves a range request inside ONE
    call (`serve`) with the interpreter let go from the wait for the
    request to the range's last byte. The C struct outlives the plane (a
    connection thread may still be in `serve` when it stops): `clear`
    closes the duplicates, the last reader of one closing it."""

    def __init__(self):
        self._t = _lib.sn_plane_table_new()

    def put(self, vid: int, sid: int, generation: int, fd: int) -> None:
        rc = _lib.sn_plane_table_put(self._t, vid, sid, generation, fd)
        if rc < 0:
            raise OSError(-rc, f"sn_plane_table_put: {os.strerror(-rc)}")

    def drop(self, vid: int, sid: int) -> None:
        _lib.sn_plane_table_drop(self._t, vid, sid)

    def clear(self) -> None:
        _lib.sn_plane_table_clear(self._t)

    def serve(self, conn_fd: int, timeout_ms: int, native_ok: bool,
              rec: np.ndarray, meta: np.ndarray) -> int:
        """Wait for the next request on `conn_fd` and serve it if it is a
        range this table holds (see native/seaweed_native.cpp); -> one
        of the PLANE_* codes, with the request and its stamps in `rec`
        (u64[len(PLANE_REC)]) and its metadata in `meta`."""
        if (rec.dtype != np.uint64 or rec.size < len(PLANE_REC)
                or meta.dtype != np.uint8 or meta.size < PLANE_META_MAX
                or not (rec.flags.c_contiguous and meta.flags.c_contiguous)):
            raise ValueError("serve wants rec u64[len(PLANE_REC)] and meta u8[PLANE_META_MAX]")
        return _lib.sn_plane_serve(
            self._t, conn_fd, timeout_ms, 1 if native_ok else 0,
            rec.ctypes.data, meta.ctypes.data,
        )


# sn_plane_fetch: what became of a range (its record's "status") ...
FETCH_STATUS = (
    "landed", "refused", "short", "bad_frame", "torn", "timed_out", "in_flight",
)
# ... and the slots of its record, as the C side numbers them
FETCH_REC = (
    "status", "answer", "n", "got", "dst", "crcs", "tail_crc", "msg_len",
    "t_sent", "t_header", "t_landed",
)
FETCH_MSG_MAX = 1024


def plane_fetch(
    fds: list[int], requests: list[bytes], sizes: list[int],
    dsts: list[np.ndarray], need: int, *, granule: int = 0,
    timeout_ms: int = -1,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Send request i on connected plane socket fds[i] for every i at
    once and land the answers (sizes[i] bytes each) in `dsts` (1-D
    C-contiguous uint8), handed out in the order the answers arrive,
    lowest index first; return once `need` ranges have landed
    or none is left to wait for (see native/seaweed_native.cpp). ->
    (record i64[n, len(FETCH_REC)], granule CRCs u32[n, max_out], refusal
    messages u8[n, FETCH_MSG_MAX]). One GIL-releasing call."""
    n = len(fds)
    if not (len(requests) == n == len(sizes) and n > 0):
        raise ValueError("plane_fetch wants one request and size per socket")
    for d in dsts:
        # the C side writes caps = nbytes from each buffer's first byte
        if not (d.dtype == np.uint8 and d.ndim == 1 and d.flags.c_contiguous):
            raise ValueError("plane_fetch lands in 1-D C-contiguous uint8 buffers")
    rec = np.zeros((n, len(FETCH_REC)), np.int64)
    max_out = (max(sizes) // granule + 2) if granule else 1
    out_crcs = np.zeros((n, max_out), np.uint32)
    msgs = np.zeros((n, FETCH_MSG_MAX), np.uint8)
    keep: list = []
    reqs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_uint32 * n)()
    for i, req in enumerate(requests):
        reqs[i], lens[i] = _part_ptr_len(req, keep)
    m = len(dsts)
    rc = _stamped(
        _lib.sn_plane_fetch, n, (ctypes.c_int * n)(*fds), reqs, lens,
        (ctypes.c_uint64 * n)(*sizes), m,
        (ctypes.c_void_p * m)(*(d.ctypes.data for d in dsts)),
        (ctypes.c_uint64 * m)(*(d.nbytes for d in dsts)),
        need, granule, ctypes.c_void_p(out_crcs.ctypes.data), max_out,
        ctypes.c_void_p(msgs.ctypes.data), FETCH_MSG_MAX, timeout_ms,
        _overlap_mode(), ctypes.c_void_p(rec.ctypes.data),
    )
    if rc < 0:
        raise OSError(-rc, f"sn_plane_fetch: {os.strerror(-rc)}")
    return rec, out_crcs, msgs


def plane_set_admissible(yes: bool) -> None:
    """Native egress admissible for every plane table of the process:
    ec/net_plane.py pushes `not faults.active()` on each arm and disarm."""
    _lib.sn_plane_set_admissible(1 if yes else 0)


def _part_ptr_len(part, keepalive: list):
    """(address, nbytes) of a bytes-like without copying it; appends
    whatever must outlive the call to `keepalive`."""
    if isinstance(part, np.ndarray):
        assert part.dtype == np.uint8 and part.flags.c_contiguous
        keepalive.append(part)
        return part.ctypes.data, part.nbytes
    if isinstance(part, bytes):
        p = ctypes.cast(ctypes.c_char_p(part), ctypes.c_void_p)
        keepalive.append((part, p))
        return p.value or 0, len(part)
    a = np.frombuffer(part, dtype=np.uint8)  # zero-copy view
    keepalive.append((part, a))
    return a.ctypes.data, a.nbytes


def sendv(out_fd: int, parts, timeout_ms: int = -1) -> int:
    """Scatter-gather write of `parts` (bytes / memoryview / uint8
    ndarray) to out_fd via writev — no Python-side join, no per-chunk
    GIL round trips. Returns total bytes sent (== sum of lengths);
    raises OSError on failure, ETIMEDOUT included, because a partial
    HTTP body is a broken connection, not a result."""
    n = len(parts)
    keep: list = []
    ptrs = (ctypes.c_void_p * n)()
    lens = (ctypes.c_uint64 * n)()
    total = 0
    for i, part in enumerate(parts):
        addr, ln = _part_ptr_len(part, keep)
        ptrs[i] = addr
        lens[i] = ln
        total += ln
    sent = _stamped(_lib.sn_sendv, out_fd, ptrs, lens, n, timeout_ms)
    if sent < 0:
        raise OSError(-sent, f"sn_sendv: {os.strerror(-sent)}")
    if sent != total:  # pragma: no cover - C side only shorts on error
        raise OSError(f"sn_sendv short write: {sent}/{total}")
    return int(sent)


def recv_overlap_active(length: int) -> bool:
    """Whether a fused recv+CRC of `length` bytes would run the
    OVERLAPPED core (socket reads on a helper thread, CRC chasing the
    landed bytes) under the current host/env. Auto: >=4 hardware
    threads AND >=256 KiB; ``SEAWEED_EC_NET_OVERLAP=1|0`` forces the
    core gate on/off (the size floor always applies). Read live, so
    the multi-core re-measure recipe can flip it per run."""
    return bool(_lib.sn_recv_overlap_active(length))


def _overlap_mode() -> int:
    """SEAWEED_EC_NET_OVERLAP -> the overlap_mode parameter of
    sn_recv_into. Read HERE (under the GIL, where os.environ mutation
    also happens) and passed down — a getenv on the C hot path would
    race a concurrent setenv, which is undefined behavior."""
    env = os.environ.get("SEAWEED_EC_NET_OVERLAP", "")
    if env == "1":
        return 1
    if env == "0":
        return 0
    return -1


def recv_into(
    fd: int,
    dst: np.ndarray,
    length: int | None = None,
    *,
    timeout_ms: int = -1,
    granule: int = 0,
    crc_state: np.ndarray | None = None,
    filled_state: np.ndarray | None = None,
    out_crcs: np.ndarray | None = None,
    out_counts: np.ndarray | None = None,
) -> int:
    """Land up to `length` bytes from fd DIRECTLY in `dst` (1-D
    C-contiguous uint8, e.g. a pooled rebuild-matrix row) — the ingress
    half of the zero-copy network plane. Returns bytes received; SHORT
    means the peer closed mid-stream (the caller's torn-stream
    contract). With granule > 0, the rolling granule-CRC32C
    (crc_state u32[1] / filled_state u64[1]) advances over the bytes
    during the copy-in, completed granule CRCs landing in out_crcs with
    the count in out_counts[0] — fused sidecar verify, no extra byte
    pass."""
    assert dst.dtype == np.uint8 and dst.ndim == 1
    assert dst.flags.c_contiguous
    if length is None:
        length = dst.nbytes
    assert 0 <= length <= dst.nbytes
    max_out = 0
    if granule:
        assert crc_state is not None and filled_state is not None
        assert out_crcs is not None and out_counts is not None
        assert crc_state.dtype == np.uint32
        assert filled_state.dtype == np.uint64
        assert out_crcs.dtype == np.uint32 and out_crcs.flags.c_contiguous
        assert out_counts.dtype == np.int32
        max_out = out_crcs.shape[-1]
    got = _stamped(
        _lib.sn_recv_into,
        fd,
        ctypes.c_void_p(dst.ctypes.data),
        length,
        timeout_ms,
        granule,
        ctypes.c_void_p(crc_state.ctypes.data) if granule else None,
        ctypes.c_void_p(filled_state.ctypes.data) if granule else None,
        ctypes.c_void_p(out_crcs.ctypes.data) if granule else None,
        ctypes.c_void_p(out_counts.ctypes.data) if granule else None,
        max_out,
        _overlap_mode(),
    )
    if got < 0:
        raise OSError(-got, f"sn_recv_into: {os.strerror(-got)}")
    return int(got)


def has_recv_file() -> bool:
    """Whether the loaded .so exports sn_recv_file (older prebuilt
    libraries may not; callers then land blob writes in Python)."""
    return _HAS_RECV_FILE


def recv_file(
    fd: int, out_fd: int, offset: int, length: int, *,
    timeout_ms: int = -1,
) -> tuple[int, int]:
    """Land `length` bytes from socket `fd` straight into file `out_fd`
    at `offset` — the write-opcode blob ingress: socket -> bounce
    buffer -> pwrite(2) with one CRC32C rolled over the payload while
    each chunk is cache-hot, no Python-side byte handling. Returns
    (bytes_landed, crc32c); SHORT means the peer closed mid-stream (the
    partial extent is on disk but callers must not ACK it). Raises
    OSError on socket or pwrite failure."""
    if not _HAS_RECV_FILE:
        raise OSError("sn_recv_file not available in loaded .so")
    crc_out = np.zeros(1, np.uint32)
    got = _lib.sn_recv_file(
        fd, out_fd, offset, length, timeout_ms,
        ctypes.c_void_p(crc_out.ctypes.data),
    )
    if got < 0:
        raise OSError(-got, f"sn_recv_file: {os.strerror(-got)}")
    return int(got), int(crc_out[0])


class NativeSink:
    """Stateful fused write+CRC sink handle (sn_sink_*): pwrite-
    positioned appends straight from caller buffers, leaf AND block
    sidecar CRC levels rolled in the same cache-hot pass, optional
    early-writeback. Callers own the fds (and their lifetime: destroy
    the sink BEFORE closing them); the sink owns only its offsets and
    CRC state."""

    EARLY_WB = 1
    DIRECT = 2

    def __init__(
        self,
        fds: list[int],
        block_size: int,
        leaf_size: int = 0,
        # Off by default: sync_file_range measured -15% on filesystems
        # whose write(2) is already synchronous (9p); the env-gated
        # policy lives in pipeline.FusedShardSink.
        early_writeback: bool = False,
        # Opt-in O_DIRECT writes while every append stays 4096-aligned
        # (pointer, width, file offset); a misaligned append (the
        # ragged tail) or a write the filesystem rejects drops that fd
        # back to buffered transparently — same bytes, same offsets.
        # Gated by SEAWEED_EC_ODIRECT in pipeline.FusedShardSink.
        direct: bool = False,
    ):
        n = len(fds)
        self.n = n
        self.block_size = block_size
        self.leaf_size = leaf_size
        flags = self.EARLY_WB if early_writeback else 0
        if direct:
            flags |= self.DIRECT
        self._h = _lib.sn_sink_create(
            (ctypes.c_int * n)(*fds), n, block_size, leaf_size, flags
        )
        if not self._h:
            raise OSError("sn_sink_create failed (bad block/leaf sizes?)")

    def direct_flags(self) -> np.ndarray:
        """Per-shard O_DIRECT state (u8[n], 1 = still direct): whether
        the page-cache-bypassing path engaged and survived alignment."""
        if self._h is None:
            raise OSError("sink already destroyed")
        out = np.zeros(self.n, np.uint8)
        _lib.sn_sink_direct_flags(self._h, ctypes.c_void_p(out.ctypes.data))
        return out

    def append(
        self,
        row_ptrs: list[int],
        width: int,
        out_block_crcs: np.ndarray,
        out_block_counts: np.ndarray,
        out_leaf_crcs: np.ndarray,
        out_leaf_counts: np.ndarray,
    ) -> None:
        if self._h is None:
            raise OSError("sink already destroyed")
        assert len(row_ptrs) == self.n
        rc = _stamped(
            _lib.sn_sink_append,
            self._h,
            (ctypes.c_void_p * self.n)(*row_ptrs),
            width,
            ctypes.c_void_p(out_block_crcs.ctypes.data),
            ctypes.c_void_p(out_block_counts.ctypes.data),
            ctypes.c_void_p(out_leaf_crcs.ctypes.data),
            ctypes.c_void_p(out_leaf_counts.ctypes.data),
            out_block_crcs.shape[1],
        )
        if rc != 0:
            raise OSError(f"sn_sink_append failed on shard {-rc - 1}")

    def finish(self) -> tuple:
        """-> (tail_block_crc, tail_block_valid, tail_leaf_crc,
        tail_leaf_valid, sizes) arrays; flushes partial-tail CRC state."""
        if self._h is None:
            raise OSError("sink already destroyed")
        n = self.n
        tb = np.zeros(n, np.uint32)
        tbv = np.zeros(n, np.uint8)
        tl = np.zeros(n, np.uint32)
        tlv = np.zeros(n, np.uint8)
        sizes = np.zeros(n, np.uint64)
        _lib.sn_sink_finish(
            self._h,
            ctypes.c_void_p(tb.ctypes.data),
            ctypes.c_void_p(tbv.ctypes.data),
            ctypes.c_void_p(tl.ctypes.data),
            ctypes.c_void_p(tlv.ctypes.data),
            ctypes.c_void_p(sizes.ctypes.data),
        )
        return tb, tbv, tl, tlv, sizes

    def destroy(self) -> None:
        if self._h is not None:
            _lib.sn_sink_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover - GC backstop
        try:
            self.destroy()
        except Exception:
            pass


def crc32c_combine(crc1: int, crc2: int, len2: int) -> int:
    """CRC of A++B from crc(A), crc(B), len(B) — the C twin of
    utils/crc.crc32c_combine (used by the sink's leaf->block fold)."""
    return _lib.sn_crc32c_combine(crc1, crc2, len2)


def gf_mul(a: int, b: int) -> int:
    return _lib.sn_gf_mul(a, b)


def has_avx2() -> bool:
    return bool(_lib.sn_has_avx2())


def scan_dat(path: str):
    """Fast .dat scan: -> (ids u64, offsets u32 [8-byte units],
    body_sizes i32, crc_ok u8) parallel arrays, append order.
    Raises OSError on unreadable/short files."""
    import os

    size = os.path.getsize(path)
    max_entries = max(size // 24 + 2, 16)  # min padded record is 24 bytes (v2 tombstone)
    ids = np.empty(max_entries, dtype=np.uint64)
    offsets = np.empty(max_entries, dtype=np.uint32)
    sizes = np.empty(max_entries, dtype=np.int32)
    crc_ok = np.empty(max_entries, dtype=np.uint8)
    n = _lib.sn_scan_dat(
        path.encode(),
        ids.ctypes.data_as(ctypes.c_void_p),
        offsets.ctypes.data_as(ctypes.c_void_p),
        sizes.ctypes.data_as(ctypes.c_void_p),
        crc_ok.ctypes.data_as(ctypes.c_void_p),
        max_entries,
    )
    if n < 0:
        raise OSError(f"sn_scan_dat({path}) failed: {n}")
    return ids[:n], offsets[:n], sizes[:n], crc_ok[:n].astype(bool)
