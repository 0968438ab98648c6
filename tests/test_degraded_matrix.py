"""The degraded read's one sibling matrix (ISSUE 27): the k sibling
extents of a reconstruction are read, verified, put and applied as ONE
contiguous (k, width) matrix. Bytes against the CpuBackend-encoded
shard; exclusion and refill of rotten rows; the fail-closed output
check; the planes that fill the rows (native batch, one by one, peers);
and the one-call granule check against `verify_range`'s loop.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import (
    BitrotProtection,
    CpuBackend,
    ECContext,
    ECError,
    EcVolume,
    ShardChecksumBuilder,
    write_ec_files,
)
from seaweedfs_tpu.ec.encoder import write_sorted_file_from_idx
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import trace

CTX = ECContext(10, 4)
LEAF = 4096
LOST = 3


def make_shards(tmp_path, seed=0):
    """A volume striped in blocks that the leaf size does not divide, so
    every shard ends in a partial granule -> (base, bytes of each shard)."""
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), 1)
    for i in range(1, 41):
        data = rng.integers(0, 256, int(rng.integers(1, 60_000)), np.uint8).tobytes()
        v.write_needle(Needle(cookie=i, needle_id=i, data=data))
    v.close()
    base = Volume.base_file_name(str(tmp_path), "", 1)
    write_sorted_file_from_idx(base)
    prot = write_ec_files(
        base, CTX, CpuBackend(CTX),
        large_block_size=50_000, small_block_size=10_000, leaf_size=LEAF,
    )
    prot.save(base + ".ecsum")
    shards = []
    for i in range(CTX.total):
        with open(base + CTX.to_ext(i), "rb") as f:
            shards.append(f.read())
    assert len(shards[0]) > 20 * LEAF and len(shards[0]) % LEAF
    return base, shards


def open_volume(tmp_path, base, lost=(LOST,), **kw):
    for sid in lost:
        os.unlink(base + CTX.to_ext(sid))
    kw.setdefault("backend_name", "cpu")
    return EcVolume(str(tmp_path), 1, **kw)


def flip(base, sid, at):
    with open(base + CTX.to_ext(sid), "r+b") as f:
        f.seek(at)
        b = f.read(1)
        f.seek(at)
        f.write(bytes([b[0] ^ 0x5A]))


def read_traced(ev, offset, size):
    """One armed degraded read -> (bytes, its span document)."""
    trace.configure(enabled=True)
    try:
        trace.reset()
        got = ev._recover_interval(LOST, offset, size)
        (doc,) = [d for d in trace.traces() if d["op"] == "ec.degraded_read"]
    finally:
        # the tracer is process-wide: leave it off and empty for whatever
        # file this worker runs next (tests/test_trace.py asserts on both)
        trace.configure(enabled=False)
        trace.reset()
    return got, doc


def extent(name, shard_len):
    return {
        "one_granule": (5 * LEAF, LEAF),
        "many_granules": (2 * LEAF, 9 * LEAF),
        "partial_tail_granule": (shard_len - 3 * LEAF - 100, 3 * LEAF + 100),
        "unaligned_offset": (3 * LEAF + 1234, 2 * LEAF + 77),
    }[name]


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize(
    "name", ["one_granule", "many_granules", "partial_tail_granule", "unaligned_offset"]
)
def test_matrix_path_bytes_equal_the_cpu_reference(tmp_path, backend, name):
    base, shards = make_shards(tmp_path)
    ev = open_volume(tmp_path, base, backend_name=backend)
    offset, size = extent(name, len(shards[LOST]))
    try:
        got, doc = read_traced(ev, offset, size)
        assert got == shards[LOST][offset : offset + size]
        # one matrix: all ten rows from the batched read, one put of it
        width = min(-(-(offset + size) // LEAF) * LEAF, len(shards[LOST])) - (
            offset // LEAF
        ) * LEAF
        assert doc["attrs"]["sibling_rows_batched"] == CTX.data_shards
        assert "sibling_rows_single" not in doc["attrs"]
        st = doc["stages"]
        assert st["sibling_read"]["count"] == 1
        assert st["crc_verify"]["count"] == 2  # the matrix, then the output row
        assert st["reconstruct"]["count"] == 1
        if backend == "tpu":
            assert doc["attrs"]["h2d_bytes"] == CTX.data_shards * width
            assert doc["attrs"]["d2h_bytes"] == width
            assert doc["attrs"]["batches"] == 1
            parts = [f"reconstruct.{p}" for p in ("put", "launch", "ready", "d2h")]
            assert all(st[p]["count"] == 1 for p in parts)
            assert "reconstruct.stack" not in st
            total = sum(st[p]["seconds"] for p in parts)
            assert 0.9 * st["reconstruct"]["seconds"] <= total <= st["reconstruct"]["seconds"]
        # unarmed: the same bytes, from the cache and afresh
        assert ev._recover_interval(LOST, offset, size) == got
        ev.interval_cache.clear()
        assert ev._recover_interval(LOST, offset, size) == got
    finally:
        ev.close()


def test_a_rotten_sibling_is_excluded_and_its_row_refilled(tmp_path):
    base, shards = make_shards(tmp_path)
    offset, size = 4 * LEAF + 10, 3 * LEAF
    flip(base, 5, 5 * LEAF + 99)  # inside the extent, among the first ten mounted
    ev = open_volume(tmp_path, base)
    try:
        got, doc = read_traced(ev, offset, size)
        assert got == shards[LOST][offset : offset + size]
        assert doc["attrs"]["sibling_rows_batched"] == 10
        assert doc["attrs"]["sibling_rows_single"] == 1  # shard 11 into shard 5's row
        # the coefficients follow the shards used: 5 is out, 11 is in
        ((target, src_ids),) = ev._coeff_cache
        assert target == LOST and 5 not in src_ids and 11 in src_ids
        assert len(src_ids) == CTX.data_shards
    finally:
        ev.close()


def test_two_rotten_siblings_of_eleven_mounted_raise(tmp_path):
    base, shards = make_shards(tmp_path)
    offset, size = 4 * LEAF, 2 * LEAF
    flip(base, 0, 4 * LEAF + 1)
    flip(base, 7, 5 * LEAF + 1)
    ev = open_volume(tmp_path, base, lost=(LOST, 12, 13))  # eleven mounted
    try:
        with pytest.raises(ECError, match="only 9 sibling shards readable"):
            ev._recover_interval(LOST, offset, size)
        assert ev.interval_cache.size_bytes == 0
    finally:
        ev.close()


def test_an_output_that_fails_its_check_raises_and_is_not_cached(tmp_path):
    base, shards = make_shards(tmp_path)
    offset, size = 6 * LEAF, 2 * LEAF
    prot = BitrotProtection.load(base + ".ecsum")
    prot.shard_leaf_crcs[LOST][7] ^= 1  # the sidecar disagrees with any output
    prot.save(base + ".ecsum")
    ev = open_volume(tmp_path, base)
    try:
        with pytest.raises(ECError, match="fails .ecsum verification"):
            ev._recover_interval(LOST, offset, size)
        assert ev.interval_cache.size_bytes == 0
        # the granules beside it still serve
        assert ev._recover_interval(LOST, 2 * LEAF, LEAF) == shards[LOST][2 * LEAF : 3 * LEAF]
    finally:
        ev.close()


@pytest.mark.parametrize("plane", ["native_off", "faults_armed"])
def test_rows_filled_one_by_one_give_the_same_bytes(tmp_path, monkeypatch, plane):
    base, shards = make_shards(tmp_path)
    offset, size = LEAF + 5, 6 * LEAF
    flip(base, 1, 2 * LEAF)  # exclusion works on this plane too
    ev = open_volume(tmp_path, base)
    try:
        if plane == "native_off":
            monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
            got, doc = read_traced(ev, offset, size)
        else:
            with faults.injected("never.hit", faults.io_error()):
                assert faults.active()
                got, doc = read_traced(ev, offset, size)
        assert got == shards[LOST][offset : offset + size]
        assert "sibling_rows_batched" not in doc["attrs"]
        assert doc["attrs"]["sibling_rows_single"] == 11  # ten kept, shard 1 dropped
    finally:
        ev.close()


def test_a_remote_reader_fills_the_missing_rows(tmp_path):
    base, shards = make_shards(tmp_path)
    offset, size = 7 * LEAF + 3, 4 * LEAF
    asked = []

    def remote_reader(sid, off, n, generation):
        asked.append(sid)
        if sid == 9:
            return None  # a peer that has nothing
        body = shards[sid][off : off + n]
        if sid == 8:  # a peer whose copy is rotten: excluded like a local one
            body = bytes([body[0] ^ 1]) + body[1:]
        return body

    # six mounted, four rows to come from peers
    ev = open_volume(
        tmp_path, base, lost=(LOST, 6, 7, 8, 9, 10, 11, 12), remote_reader=remote_reader
    )
    try:
        got, doc = read_traced(ev, offset, size)
        assert got == shards[LOST][offset : offset + size]
        assert doc["attrs"]["sibling_rows_batched"] == 6
        assert 4 <= doc["attrs"]["sibling_rows_single"] <= 5
        assert LOST not in asked and set(asked) <= {6, 7, 8, 9, 10, 11, 12}
        ((_target, src_ids),) = ev._coeff_cache
        assert 8 not in src_ids and 9 not in src_ids
    finally:
        ev.close()


def test_a_wide_extent_takes_the_staged_apply_from_the_same_matrix(tmp_path, monkeypatch):
    from seaweedfs_tpu.ec import ec_volume

    base, shards = make_shards(tmp_path)
    monkeypatch.setattr(ec_volume, "STAGED_RECOVERY_BATCH", 2 * LEAF)
    flip(base, 2, 9 * LEAF)
    ev = open_volume(tmp_path, base)
    try:
        size = len(shards[LOST]) - LEAF
        got, doc = read_traced(ev, LEAF, size)
        assert got == shards[LOST][LEAF:]
        assert "reconstruct" not in doc["stages"]  # batches, not the single shot
        assert doc["attrs"]["sibling_rows_single"] == 1
    finally:
        ev.close()


# ------------------------------------------- the one-call granule check


def _sidecar(rows, granule, block):
    builders = []
    for row in rows:
        b = ShardChecksumBuilder(block_size=block, leaf_size=granule)
        b.write(row.tobytes())
        builders.append(b)
    return BitrotProtection.from_builders(ECContext(len(rows) - 1, 1), builders)


@pytest.mark.parametrize("seed", range(12))
def test_verify_rows_agrees_with_verify_ranges_loop(seed):
    """Random granule sizes, shard tails and granule-aligned ranges; a
    flipped byte in any granule of any row; v1 (blocks only) as well."""
    rng = np.random.default_rng(seed)
    granule = int(rng.choice([64, 512, 4096]))
    block = granule * int(rng.choice([1, 4]))
    n_rows = int(rng.integers(2, 6))
    shard_len = int(rng.integers(granule, 12 * granule)) + int(rng.integers(0, granule))
    shards = rng.integers(0, 256, (n_rows, shard_len), dtype=np.uint8)
    prot = _sidecar(shards, granule if seed % 3 else 0, block)
    gsize, _ = prot.verify_granularity(0)
    ids = list(range(n_rows))
    for _ in range(25):
        lo = int(rng.integers(0, -(-shard_len // gsize))) * gsize
        # whole granules, or on into the shard's partial tail granule
        hi = min(shard_len, lo + int(rng.integers(1, 14)) * gsize)
        rows = shards[:, lo:hi].copy()
        assert prot.verify_rows(ids, lo, rows) == [True] * n_rows
        assert all(prot.verify_range(i, lo, rows[i].tobytes()) for i in ids)
        # one flipped byte: that row fails in both, the others pass
        r, at = int(rng.integers(0, n_rows)), int(rng.integers(0, hi - lo))
        rows[r, at] ^= 1 << int(rng.integers(0, 8))
        want = [prot.verify_range(i, lo, rows[i].tobytes()) for i in ids]
        assert want == [i != r for i in ids]
        assert prot.verify_rows(ids, lo, rows) == want
        # a strided view (rows of a wider matrix) reads the same
        wide = np.zeros((n_rows, hi - lo + 7), dtype=np.uint8)
        wide[:, : hi - lo] = rows
        assert prot.verify_rows(ids, lo, wide[:, : hi - lo]) == want
    # a range that stops inside a granule which is not the tail: False
    if shard_len > gsize + 1:
        cut = shards[:, : gsize // 2].copy()
        assert prot.verify_rows(ids, 0, cut) == [False] * n_rows
        assert not any(prot.verify_range(i, 0, cut[i].tobytes()) for i in ids)
    # past the sidecar's record, and an id it does not know: False, no raise
    assert prot.verify_rows([0], (len(prot.verify_granularity(0)[1]) + 1) * gsize,
                            shards[:1, :gsize]) == [False]
    assert prot.verify_rows([0, 99], 0, shards[:2, :gsize]) == [True, False]
    assert prot.verify_range(99, 0, shards[1, :gsize].tobytes()) is False


def test_granule_crcs_without_the_native_core(monkeypatch):
    from seaweedfs_tpu.utils import crc

    rows = np.random.default_rng(3).integers(0, 256, (3, 1000), dtype=np.uint8)
    native = crc.crc32c_granules(rows, 256)
    monkeypatch.setattr(crc, "_native_crc", False)
    assert np.array_equal(crc.crc32c_granules(rows, 256), native)
    assert native.shape == (3, 4)
    assert int(native[2, 3]) == crc.crc32c(rows[2, 768:].tobytes())
