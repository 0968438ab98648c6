"""CRC32C (Castagnoli) — needle checksums and the .ecsum bitrot sidecar.

The reference uses CRC32-Castagnoli for both needle checksums and the
per-shard-block bitrot sums (weed/storage/needle/crc.go,
weed/storage/erasure_coding/ec_bitrot.go). Uses the C++ native core
(native/libseaweed_native.so, hardware CRC32C when available) and falls
back to a numpy slice-by-8 table implementation.
"""

from __future__ import annotations

import functools

import numpy as np

CASTAGNOLI_POLY = 0x82F63B78  # reflected


def _make_tables(n: int = 8) -> np.ndarray:
    t = np.zeros((n, 256), dtype=np.uint32)
    for i in range(256):
        crc = i
        for _ in range(8):
            crc = (crc >> 1) ^ (CASTAGNOLI_POLY if crc & 1 else 0)
        t[0, i] = crc
    for k in range(1, n):
        for i in range(256):
            t[k, i] = (t[k - 1, i] >> 8) ^ t[0, t[k - 1, i] & 0xFF]
    return t


_TABLES = _make_tables()


def _crc32c_py(data: bytes | np.ndarray, crc: int = 0) -> int:
    """Slice-by-8 in a python loop over 8-byte strides (fallback path)."""
    buf = np.frombuffer(bytes(data), dtype=np.uint8)
    crc = (~crc) & 0xFFFFFFFF
    t = _TABLES
    n = len(buf)
    i = 0
    # process unaligned prefix bytewise
    while i < n and i % 8 != 0:
        crc = (crc >> 8) ^ int(t[0, (crc ^ buf[i]) & 0xFF])
        i += 1
    n8 = (n - i) // 8
    if n8:
        words = buf[i : i + n8 * 8].reshape(n8, 8)
        for row in words:
            w = crc ^ int(row[0]) ^ (int(row[1]) << 8) ^ (int(row[2]) << 16) ^ (
                int(row[3]) << 24
            )
            crc = (
                int(t[7, w & 0xFF])
                ^ int(t[6, (w >> 8) & 0xFF])
                ^ int(t[5, (w >> 16) & 0xFF])
                ^ int(t[4, (w >> 24) & 0xFF])
                ^ int(t[3, int(row[4])])
                ^ int(t[2, int(row[5])])
                ^ int(t[1, int(row[6])])
                ^ int(t[0, int(row[7])])
            )
        i += n8 * 8
    while i < n:
        crc = (crc >> 8) ^ int(t[0, (crc ^ int(buf[i])) & 0xFF])
        i += 1
    return (~crc) & 0xFFFFFFFF


_native_crc = None


def _load_native():
    global _native_crc
    if _native_crc is None:
        try:
            from . import native

            _native_crc = native.crc32c
        except Exception:
            _native_crc = False
    return _native_crc


def crc32c(data, crc: int = 0) -> int:
    """CRC32C of `data`, optionally continuing from a previous value."""
    fn = _load_native()
    if fn:
        return fn(data, crc)
    return _crc32c_py(data, crc)


def crc32c_granules(rows: np.ndarray, granule: int) -> np.ndarray:
    """CRC32C of every `granule`-byte piece of each row of a 2-D uint8
    matrix (a row's last piece may be short) -> u32[rows, pieces]: one
    native call for the lot, a loop over `crc32c` without the native
    core."""
    if _load_native():
        from . import native

        return native.crc32c_granules(rows, granule)
    out = np.empty((len(rows), -(-rows.shape[1] // granule)), dtype=np.uint32)
    for r, row in enumerate(rows):
        for g in range(out.shape[1]):
            out[r, g] = _crc32c_py(row[g * granule : (g + 1) * granule])
    return out


# ---------------------------------------------------------------- combine
#
# crc32c(A || B) from crc32c(A), crc32c(B), len(B) without touching the
# bytes (zlib's crc32_combine GF(2) matrix method, Castagnoli polynomial).
# Lets the .ecsum v2 sidecar derive block-level CRCs from its per-leaf
# CRCs in one pass: each leaf is checksummed independently while
# cache-hot, and the 16 MiB block CRC is folded from the leaf CRCs in
# O(leaves * 32) XORs instead of re-reading the block.


def _gf2_matrix_times(mat: list[int], vec: int) -> int:
    out = 0
    i = 0
    while vec:
        if vec & 1:
            out ^= mat[i]
        vec >>= 1
        i += 1
    return out


def _gf2_matrix_square(mat: list[int]) -> list[int]:
    return [_gf2_matrix_times(mat, mat[n]) for n in range(32)]


def _gf2_matrix_mul(a: list[int], b: list[int]) -> list[int]:
    """Operator composition: (a∘b)[n] = a * b[n] (columns are uint32)."""
    return [_gf2_matrix_times(a, b[n]) for n in range(32)]


@functools.lru_cache(maxsize=64)
def _zero_operator(nbytes: int) -> tuple[int, ...]:
    """32x32 GF(2) matrix advancing a finalized CRC32C over `nbytes`
    zero bytes. Cached per length: .ecsum leaves are uniform-size, so a
    whole sidecar's combines reuse one or two cached operators."""
    odd = [0] * 32
    odd[0] = CASTAGNOLI_POLY  # one zero BIT, reflected form
    row = 1
    for n in range(1, 32):
        odd[n] = row
        row <<= 1
    even = _gf2_matrix_square(odd)  # 2 bits
    odd = _gf2_matrix_square(even)  # 4 bits
    mat = odd
    op: list[int] | None = None
    n = nbytes
    while n:
        mat = _gf2_matrix_square(mat)  # 8 bits = 1 byte, then doubling
        if n & 1:
            op = list(mat) if op is None else _gf2_matrix_mul(mat, op)
        n >>= 1
    assert op is not None  # nbytes > 0 guaranteed by caller
    return tuple(op)


def crc32c_combine(crc1: int, crc2: int, len2: int) -> int:
    """crc32c of the concatenation of two streams whose individual
    (finalized) CRCs are crc1 and crc2, where the second stream is
    `len2` bytes long."""
    if len2 <= 0:
        return crc1
    return _gf2_matrix_times(list(_zero_operator(len2)), crc1) ^ crc2
