"""The plain reference: what a 10+4 `ec.encode` of a .dat has to produce.

Written from the published formats and from nothing of the program:
Reed-Solomon over GF(2^8) (polynomial 0x11d) with klauspost/reedsolomon's
systematic matrix (Vandermonde rows r^c times the inverse of its top
square), SeaweedFS's striping of a .dat into rows of `large_block` and
then `small_block` blocks, zero-padded, and a CRC32C (google_crc32c) per
bitrot block and per leaf as the `.ecsum` sidecar records them. numpy on
the host, table look-ups, no kernels.
"""

from __future__ import annotations

import dataclasses
import struct
from concurrent.futures import ThreadPoolExecutor

import google_crc32c
import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(_EXP[_LOG[a] + _LOG[b]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(_EXP[(_LOG[a] * n) % 255])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(256)")
    return int(_EXP[255 - _LOG[a]])


def _mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = [[0] * len(b[0]) for _ in a]
    for i, row in enumerate(a):
        for j in range(len(b[0])):
            acc = 0
            for t, v in enumerate(row):
                acc ^= gf_mul(v, b[t][j])
            out[i][j] = acc
    return out


def _mat_inv(m: list[list[int]]) -> list[list[int]]:
    n = len(m)
    aug = [list(row) + [int(i == j) for j in range(n)] for i, row in enumerate(m)]
    for col in range(n):
        piv = next(r for r in range(col, n) if aug[r][col])
        aug[col], aug[piv] = aug[piv], aug[col]
        inv = gf_inv(aug[col][col])
        aug[col] = [gf_mul(v, inv) for v in aug[col]]
        for r in range(n):
            if r != col and aug[r][col]:
                f = aug[r][col]
                aug[r] = [v ^ gf_mul(f, w) for v, w in zip(aug[r], aug[col])]
    return [row[n:] for row in aug]


def coding_matrix(k: int, m: int) -> list[list[int]]:
    """(k+m) x k: identity on top, parity rows below."""
    vm = [[gf_pow(r, c) for c in range(k)] for r in range(k + m)]
    return _mat_mul(vm, _mat_inv(vm[:k]))


def mul_table(c: int) -> np.ndarray:
    return np.array([gf_mul(c, x) for x in range(256)], dtype=np.uint8)


def parity_of(
    data: np.ndarray, k: int, m: int, drop_term: tuple[int, int] | None = None
) -> np.ndarray:
    """(m, width) parity of (k, width) data. Each data row is looked up
    once in a table of 32-bit words that packs its product with the m
    coefficients of its column (m <= 4), so the rows XOR together in one
    pass. `drop_term=(parity_row, data_row)` leaves that one product out:
    the control's broken encode."""
    if m > 4:
        raise ValueError("the packed look-up holds at most 4 parity rows")
    rows = coding_matrix(k, m)[k:]
    width = data.shape[1]
    out = np.zeros(width, dtype=np.uint32)
    for c in range(k):
        packed = np.zeros(256, dtype=np.uint32)
        for p in range(m):
            if drop_term == (p, c):
                continue
            packed |= mul_table(rows[p][c]).astype(np.uint32) << (8 * p)
        out ^= packed[data[c]]
    return np.stack([((out >> (8 * p)) & 0xFF).astype(np.uint8) for p in range(m)])


@dataclasses.dataclass
class Encoded:
    """What the reference says an encode produces. A shard is a list of
    pieces in file order: views of the .dat for the data shards (no
    copy is made of them), arrays of parity for the others."""

    pieces: list[list[np.ndarray]]  # k+m shards
    block_crcs: list[list[int]]
    leaf_crcs: list[list[int]]

    def shard_size(self, i: int) -> int:
        return sum(len(p) for p in self.pieces[i])


def stripe_rows(
    dat: np.ndarray, k: int, large_block: int, small_block: int
) -> list[np.ndarray]:
    """The .dat as (k, block) rows: rows of k large blocks while a whole
    such row is left, then rows of k small blocks, the last zero-padded.
    Data shard i is row[i] of every row in turn."""
    rows: list[np.ndarray] = []
    pos, left = 0, len(dat)
    while left >= large_block * k:
        rows.append(dat[pos : pos + large_block * k].reshape(k, large_block))
        pos += large_block * k
        left -= large_block * k
    while left > 0:
        take = min(small_block * k, left)
        chunk = dat[pos : pos + take]
        if take < small_block * k:
            padded = np.zeros(small_block * k, dtype=np.uint8)
            padded[:take] = chunk
            chunk = padded
        rows.append(chunk.reshape(k, small_block))
        pos += take
        left -= take
    return rows


def _stream_crcs(pieces: list[np.ndarray], step: int) -> list[int]:
    """CRC32C of every `step` bytes of the pieces laid end to end."""
    out, crc, filled = [], 0, 0
    for piece in pieces:
        o = 0
        while o < len(piece):
            n = min(step - filled, len(piece) - o)
            # google_crc32c takes an array, and refuses a memoryview of one
            crc = google_crc32c.extend(crc, piece[o : o + n])
            o += n
            filled += n
            if filled == step:
                out.append(crc)
                crc, filled = 0, 0
    if filled:
        out.append(crc)
    return out


def encode(
    dat: np.ndarray, layout: dict, drop_term: tuple[int, int] | None = None,
    threads: int = 8,
) -> Encoded:
    """Reference encode of one .dat under a configuration's `layout`."""
    k, m = int(layout["data_shards"]), int(layout["parity_shards"])
    rows = stripe_rows(
        dat, k, int(layout["large_block_bytes"]), int(layout["small_block_bytes"])
    )
    # parity in column chunks small enough to stay in the cache
    width = 1 << 18

    def row_parity(row: np.ndarray) -> np.ndarray:
        out = np.empty((m, row.shape[1]), dtype=np.uint8)
        for lo in range(0, row.shape[1], width):
            out[:, lo : lo + width] = parity_of(row[:, lo : lo + width], k, m, drop_term)
        return out

    with ThreadPoolExecutor(max_workers=threads) as ex:
        parity = list(ex.map(row_parity, rows))
    pieces = [[row[i] for row in rows] for i in range(k)]
    pieces += [[par[p] for par in parity] for p in range(m)]
    block, leaf = int(layout["bitrot_block_bytes"]), int(layout["bitrot_leaf_bytes"])
    with ThreadPoolExecutor(max_workers=threads) as ex:
        block_crcs = list(ex.map(lambda ps: _stream_crcs(ps, block), pieces))
        leaf_crcs = list(ex.map(lambda ps: _stream_crcs(ps, leaf), pieces))
    return Encoded(pieces, block_crcs, leaf_crcs)


# -------------------------------------------------------------- sidecar


@dataclasses.dataclass
class Sidecar:
    block_size: int
    shard_sizes: list[int]
    block_crcs: list[list[int]]
    leaf_size: int
    leaf_crcs: list[list[int]]


def parse_ecsum(raw: bytes) -> Sidecar:
    """The `.ecsum` file as its format is published (ec/bitrot.py's
    docstring): 14-byte header, then the little-endian payload."""
    if len(raw) < 14:
        raise ValueError(".ecsum shorter than its header")
    version, plen, pcrc = struct.unpack("<HII", raw[4:14])
    payload = raw[14 : 14 + plen]
    if len(payload) != plen or google_crc32c.value(payload) != pcrc:
        raise ValueError(".ecsum payload is truncated or fails its own CRC")
    block_size, _gen, k, m = struct.unpack("<IQBB", payload[:14])
    p = 30  # 14 bytes of fields and a 16-byte uuid
    sizes, crcs = [], []
    for _ in range(k + m):
        size, count = struct.unpack("<QI", payload[p : p + 12])
        p += 12
        crcs.append(list(struct.unpack(f"<{count}I", payload[p : p + 4 * count])))
        p += 4 * count
        sizes.append(size)
    leaf_size, leaf_crcs = 0, []
    if version >= 2:
        (leaf_size,) = struct.unpack("<I", payload[p : p + 4])
        p += 4
        for _ in range(k + m):
            (count,) = struct.unpack("<I", payload[p : p + 4])
            p += 4
            leaf_crcs.append(
                list(struct.unpack(f"<{count}I", payload[p : p + 4 * count]))
            )
            p += 4 * count
    return Sidecar(block_size, sizes, crcs, leaf_size, leaf_crcs)


def shard_ext(i: int) -> str:
    return f".ec{i:02d}"


def compare_shards(base: str, want: Encoded, shard_ids=None) -> int:
    """Shard files `<base>.ecNN` that differ from the reference's or
    are missing."""
    ids = range(len(want.pieces)) if shard_ids is None else shard_ids
    differing = 0
    for i in ids:
        try:
            got = np.memmap(base + shard_ext(i), dtype=np.uint8, mode="r")
        except (OSError, ValueError):
            differing += 1
            continue
        same = len(got) == want.shard_size(i)
        o = 0
        for piece in want.pieces[i]:
            if not same:
                break
            same = np.array_equal(got[o : o + len(piece)], piece)
            o += len(piece)
        differing += 0 if same else 1
        del got
    return differing


def compare_encoding(base: str, want: Encoded, shard_ids=None) -> tuple[int, int]:
    """(shard files that differ or are missing, .ecsum fields that
    differ) of the program's files at `base` against the reference."""
    try:
        with open(base + ".ecsum", "rb") as f:
            raw = f.read()
    except OSError:
        return compare_shards(base, want, shard_ids), 4
    return compare_shards(base, want, shard_ids), compare_sidecar(raw, want)


def compare_sidecar(raw: bytes, want: Encoded) -> int:
    """Fields of an `.ecsum` that differ from the reference's."""
    try:
        side = parse_ecsum(raw)
    except (ValueError, struct.error):
        return 4
    fields = [
        side.shard_sizes != [want.shard_size(i) for i in range(len(want.pieces))],
        side.block_crcs != want.block_crcs,
        side.leaf_crcs != want.leaf_crcs,
        not side.leaf_crcs,
    ]
    return sum(fields)
