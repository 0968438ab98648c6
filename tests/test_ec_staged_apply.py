"""PR 3 staged-apply tests: `apply_staged` on every backend family
(CPU, XLA, interpret-mode Pallas, column mesh, CPU-fallback shim), the
shared `run_staged_apply` driver, the staged rebuild/decode/degraded
paths, the generation-keyed interval cache, the leaf-granular scrub
cursor, and the retry-policy sweep.

Bit-identity is the load-bearing property everywhere: the staged path
must produce byte-for-byte what the synchronous `apply` produces, on
every backend, for every batch shape — including ragged tails — and
through a mid-stream device failure.
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
    FallbackBackend,
    JaxBackend,
    ec_decode_volume,
    ec_encode_volume,
    rebuild_ec_files,
    scrub_ec_volume,
)
from seaweedfs_tpu.ec.backend import _decode_coeffs
from seaweedfs_tpu.ec.pipeline import run_staged_apply
from seaweedfs_tpu.ec.scrub import ScrubCursor
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils.retry import CircuitBreaker, RetryPolicy

CTX = ECContext(10, 4)
K = CTX.data_shards


def make_volume(tmp_path, vid=1, needles=30, seed=0):
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), vid)
    payloads = {}
    for i in range(1, needles + 1):
        size = int(rng.integers(1, 60_000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x1000 + i, needle_id=i, data=data))
        payloads[i] = data
    v.close()
    return Volume.base_file_name(str(tmp_path), "", vid), payloads


def decode_coeffs(targets, src):
    rs = gf256.ReedSolomon(CTX.data_shards, CTX.parity_shards)
    return _decode_coeffs(rs.matrix, K, tuple(targets), tuple(src))


def make_backend(kind):
    if kind == "cpu":
        return CpuBackend(CTX)
    if kind == "xla":
        return JaxBackend(CTX, impl="xla", n_devices=1)
    if kind == "pallas_interpret":
        return JaxBackend(CTX, impl="pallas", interpret=True, n_devices=1)
    if kind == "mesh":
        return JaxBackend(CTX)  # conftest forces 8 virtual devices
    if kind == "fallback":
        return FallbackBackend(
            JaxBackend(CTX, impl="xla", n_devices=1), CpuBackend(CTX)
        )
    raise AssertionError(kind)


BACKENDS = ["cpu", "xla", "pallas_interpret", "mesh", "fallback"]


# ------------------------------------------------- staged apply bit-identity


@pytest.mark.parametrize("kind", BACKENDS)
def test_apply_staged_bit_identical_across_widths(kind):
    """CPU truth vs the staged path on every backend, across batch
    shapes including sub-lane and ragged widths."""
    be = make_backend(kind)
    cpu = CpuBackend(CTX)
    rng = np.random.default_rng(42)
    coeffs = decode_coeffs((0, 13), tuple(range(1, 11)))
    for width in (1, 127, 1000, 4096, 65_536 + 13):
        data = rng.integers(0, 256, (K, width), dtype=np.uint8)
        want = cpu.apply(coeffs, data)
        got = be.to_host(be.apply_staged(coeffs, be.to_device(data)))
        assert got.dtype == np.uint8 and got.shape == want.shape
        assert np.array_equal(got, want), (kind, width)


@pytest.mark.parametrize("kind", ["cpu", "xla", "mesh", "fallback"])
def test_run_staged_apply_driver_ragged_tail(kind):
    """The shared driver over multiple batches with a ragged tail must
    concatenate to exactly the single-shot apply output, with tags
    delivered in order."""
    be = make_backend(kind)
    cpu = CpuBackend(CTX)
    rng = np.random.default_rng(7)
    coeffs = decode_coeffs((2,), tuple(i for i in range(14) if i != 2)[:K])
    src = tuple(i for i in range(14) if i != 2)[:K]
    total = 3 * 4096 + 1234  # ragged final batch
    data = rng.integers(0, 256, (K, total), dtype=np.uint8)
    want = cpu.apply(coeffs, data)

    out = np.zeros((1, total), dtype=np.uint8)
    tags = []

    def produce():
        for off in range(0, total, 4096):
            yield off, data[:, off : off + 4096]

    def consume(off, rec):
        tags.append(off)
        out[:, off : off + rec.shape[1]] = rec

    run_staged_apply(be, coeffs, produce, consume, describe="test staged")
    assert tags == sorted(tags) == list(range(0, total, 4096))
    assert np.array_equal(out, want)
    assert src  # silence linters: src documents the coeff layout


def test_run_staged_apply_passthrough():
    """coeffs=None is the decode configuration: items flow through
    untouched (no device round-trip), order preserved."""
    items = [(i, bytes([i]) * 100) for i in range(20)]
    got = []
    run_staged_apply(
        None, None, lambda: iter(items), lambda tag, b: got.append((tag, b))
    )
    assert got == items


# ------------------------------------------------------------ staged rebuild


@pytest.mark.parametrize("kind", ["cpu", "xla", "fallback", "mesh"])
def test_rebuild_equals_the_cpu_encodes_shards(tmp_path, kind):
    """A rebuild publishes, on every backend family, the bytes the CPU
    encode wrote (and they verify against the sidecar)."""
    base, _ = make_volume(tmp_path, needles=20, seed=3)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    missing = [1, K + 1]
    originals = {}
    for i in missing:
        with open(base + CTX.to_ext(i), "rb") as f:
            originals[i] = f.read()
        os.unlink(base + CTX.to_ext(i))

    assert rebuild_ec_files(
        base, backend=make_backend(kind), batch_size=100_000
    ) == sorted(missing)
    for i in missing:
        with open(base + CTX.to_ext(i), "rb") as f:
            assert f.read() == originals[i], (kind, i)


# ----------------------------------------- chaos: device fault mid-staged


@pytest.mark.chaos
def test_apply_staged_fault_falls_back_bit_identical(tmp_path):
    """A device fault fired at ec.backend.device.apply_staged mid-rebuild:
    the batch degrades to CPU through the carried host copy, the rebuilt
    shards are bit-identical, and the window is not lost."""
    base, _ = make_volume(tmp_path, needles=20, seed=4)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    missing = [2, 12]
    originals = {}
    for i in missing:
        with open(base + CTX.to_ext(i), "rb") as f:
            originals[i] = f.read()
        os.unlink(base + CTX.to_ext(i))

    fb = FallbackBackend(
        JaxBackend(CTX, impl="xla", n_devices=1),
        CpuBackend(CTX),
        breaker=CircuitBreaker(failure_threshold=3, reset_timeout=9999.0),
    )
    with faults.injected(
        "ec.backend.device.apply_staged",
        faults.io_error("device lost mid-apply"),
        when=faults.nth_call(2),
        count=1,
    ):
        # chaos-armed registries route rebuild through the byte path;
        # drive the staged surface directly instead
        coeffs = decode_coeffs((0,), tuple(range(1, 11)))
        rng = np.random.default_rng(0)
        outs = []
        for _ in range(4):
            data = rng.integers(0, 256, (K, 8192), dtype=np.uint8)
            outs.append(
                (data, fb.to_host(fb.apply_staged(coeffs, fb.to_device(data))))
            )
    cpu = CpuBackend(CTX)
    for data, got in outs:
        assert np.array_equal(got, cpu.apply(coeffs, data))
    assert fb.fallback_batches >= 1, "fault never engaged the fallback"
    # registry is clean again: the real rebuild takes the fused path
    assert rebuild_ec_files(base, backend=fb) == sorted(missing)
    for i in missing:
        with open(base + CTX.to_ext(i), "rb") as f:
            assert f.read() == originals[i]


@pytest.mark.chaos
def test_apply_staged_repeated_faults_open_breaker():
    """Every staged dispatch failing opens the breaker; output stays
    bit-identical throughout (CPU serves)."""
    fb = FallbackBackend(
        JaxBackend(CTX, impl="xla", n_devices=1),
        CpuBackend(CTX),
        breaker=CircuitBreaker(failure_threshold=3, reset_timeout=9999.0),
    )
    cpu = CpuBackend(CTX)
    coeffs = decode_coeffs((5,), tuple(i for i in range(14) if i != 5)[:K])
    rng = np.random.default_rng(1)
    with faults.injected(
        "ec.backend.device.apply_staged", faults.io_error("device dead")
    ):
        for _ in range(5):
            data = rng.integers(0, 256, (K, 2048), dtype=np.uint8)
            got = fb.to_host(fb.apply_staged(coeffs, fb.to_device(data)))
            assert np.array_equal(got, cpu.apply(coeffs, data))
    assert fb.breaker.state == "open"
    assert fb.fallback_batches >= 3


@pytest.mark.chaos
def test_staged_to_host_fault_recomputes_apply_not_encode():
    """A to_host failure on an APPLY handle must replay the apply (with
    its coefficients), not an encode — the handle kind is load-bearing."""
    fb = FallbackBackend(
        JaxBackend(CTX, impl="xla", n_devices=1),
        CpuBackend(CTX),
        breaker=CircuitBreaker(failure_threshold=99, reset_timeout=9999.0),
    )
    cpu = CpuBackend(CTX)
    coeffs = decode_coeffs((3, 7), tuple(i for i in range(14) if i not in (3, 7))[:K])
    data = np.random.default_rng(2).integers(0, 256, (K, 4096), dtype=np.uint8)
    with faults.injected(
        "ec.backend.device.to_host", faults.io_error("drain failed"), count=1
    ):
        got = fb.to_host(fb.apply_staged(coeffs, fb.to_device(data)))
    assert fb.fallback_batches == 1
    assert np.array_equal(got, cpu.apply(coeffs, data))
    # and an encode handle still re-encodes
    with faults.injected(
        "ec.backend.device.to_host", faults.io_error("drain failed"), count=1
    ):
        got = fb.to_host(fb.encode_staged(fb.to_device(data)))
    assert np.array_equal(got, cpu.encode(data))


# -------------------------------------------------- staged degraded reads


def test_degraded_reads_use_staged_path_bit_exact(tmp_path, monkeypatch):
    """Wide degraded extents go through run_staged_apply (batched); all
    payloads must come back bit-exact. Shrinking the batch threshold
    forces every reconstruction through the staged path."""
    import seaweedfs_tpu.ec.ec_volume as ecv

    base, payloads = make_volume(tmp_path, needles=12, seed=5)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    os.unlink(base + CTX.to_ext(0))
    monkeypatch.setattr(ecv, "STAGED_RECOVERY_BATCH", 2048)
    ev = EcVolume(str(tmp_path), 1, backend_name="cpu")
    try:
        for i, data in payloads.items():
            assert ev.read_needle(i, cookie=0x1000 + i).data == data
    finally:
        ev.close()


# ------------------------------------------------- degraded decode self-heal


def test_decode_with_missing_data_shard_self_heals(tmp_path):
    """ec_decode_volume with a lost DATA shard regenerates it through
    the staged rebuild (instead of refusing) and the decoded .dat is
    byte-identical to the original volume."""
    base, _ = make_volume(tmp_path, needles=15, seed=6)
    with open(base + ".dat", "rb") as f:
        original_dat = f.read()
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    os.unlink(base + ".dat")
    os.unlink(base + CTX.to_ext(3))  # a data shard
    assert ec_decode_volume(base, CTX, backend=CpuBackend(CTX)) is True
    with open(base + ".dat", "rb") as f:
        decoded = f.read()
    assert decoded == original_dat[: len(decoded)]
    assert len(decoded) >= len(original_dat) - 8  # padding-trim envelope
    # the regenerated shard was published (self-heal side effect)
    assert os.path.exists(base + CTX.to_ext(3))


def test_decode_repairs_rotten_present_shard(tmp_path):
    """A data shard present ON DISK but bitrotten must not be de-striped
    into the .dat: decode's upfront rebuild pass verifies every present
    shard against the sidecar, replaces the rotten one, and the decoded
    volume is bit-exact."""
    base, _ = make_volume(tmp_path, needles=15, seed=9)
    with open(base + ".dat", "rb") as f:
        original_dat = f.read()
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    os.unlink(base + ".dat")
    flip_byte(base + CTX.to_ext(2), 12345, 0x40)  # rot a DATA shard
    assert ec_decode_volume(base, CTX, backend=CpuBackend(CTX)) is True
    with open(base + ".dat", "rb") as f:
        decoded = f.read()
    assert decoded == original_dat[: len(decoded)]


def test_decode_below_k_still_fails_closed(tmp_path):
    base, _ = make_volume(tmp_path, needles=10, seed=7)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    for i in range(CTX.parity_shards + 1):  # > parity losses
        os.unlink(base + CTX.to_ext(i))
    with pytest.raises(ECError):
        ec_decode_volume(base, CTX, backend=CpuBackend(CTX))


# -------------------------------------------- generation-keyed interval cache


def degraded_volume(tmp_path, lost=0):
    base, payloads = make_volume(tmp_path, needles=30, seed=8)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    os.unlink(base + CTX.to_ext(lost))
    ev = EcVolume(str(tmp_path), 1, backend_name="cpu")
    return base, payloads, ev


def test_unrelated_shard_remount_keeps_cache(tmp_path):
    """Remounting a shard UNRELATED to the cached extents must keep
    them (the wholesale clear() this replaces dropped everything) —
    repeats still hit the cache and re-read zero sibling bytes."""
    base, payloads, ev = degraded_volume(tmp_path)
    try:
        for i, data in payloads.items():
            assert ev.read_needle(i, cookie=0x1000 + i).data == data
        cached = ev.interval_cache.size_bytes
        assert cached > 0
        ev.reopen_shards([5])  # unrelated, live shard
        assert ev.interval_cache.size_bytes == cached
        h0, b0 = ev.interval_cache.hits, ev.bytes_read
        for i, data in payloads.items():
            assert ev.read_needle(i, cookie=0x1000 + i).data == data
        assert ev.interval_cache.hits > h0
        # lost-shard extents all served from cache: no sibling re-reads
        # beyond the live-shard intervals of each needle
        assert ev.bytes_read - b0 < b0
    finally:
        ev.close()


def test_affected_shard_events_still_invalidate(tmp_path):
    """The existing invalidation contract holds when the AFFECTED shard
    is the one remounted/unmounted, and deletes stay wholesale."""
    base, payloads, ev = degraded_volume(tmp_path)
    try:
        nid = next(iter(payloads))
        ev.read_needle(nid, cookie=0x1000 + nid)
        assert ev.interval_cache.size_bytes > 0
        gen0 = ev._shard_gen.get(0, 0)
        ev.reopen_shards([0])  # the lost shard (e.g. post-rebuild)
        assert ev.interval_cache.size_bytes == 0
        assert ev._shard_gen[0] == gen0 + 1
        ev.read_needle(nid, cookie=0x1000 + nid)
        assert ev.interval_cache.size_bytes > 0
        ev.delete_needle(max(payloads))  # content change: wholesale
        assert ev.interval_cache.size_bytes == 0
    finally:
        ev.close()


def test_stale_generation_put_is_invisible(tmp_path):
    """An in-flight reconstruction that populates under a pre-bump
    generation must be invisible to post-bump reads (the race the
    generation key closes)."""
    base, payloads, ev = degraded_volume(tmp_path)
    try:
        nid = next(iter(payloads))
        ev.read_needle(nid, cookie=0x1000 + nid)
        keys0 = {k for k in ev.interval_cache._data}
        # keys are "<ns><sid>:<gen>:<lo>:<hi>" with ns = "<vid>:"
        assert all(k.split(":")[2] == "0" for k in keys0)
        ev.unmount_shards([0])  # bump shard 0's generation
        # simulate the in-flight put landing late under the old gen
        stale = "1:0:0:0:4096"
        ev.interval_cache.put(stale, b"x" * 4096)
        h0 = ev.interval_cache.hits
        ev.read_needle(nid, cookie=0x1000 + nid)  # re-reconstructs
        new_keys = {k for k in ev.interval_cache._data if k != stale}
        assert all(k.split(":")[2] == "1" for k in new_keys)
        assert ev.interval_cache.hits == h0  # stale entry never hit
    finally:
        ev.close()


# ------------------------------------------------ leaf-granular scrub cursor


def synth_leafy_shards(tmp_path, shard_size=8 * 4096, block_size=4 * 4096,
                       leaf_size=4096, seed=0):
    """RS-consistent shards + v2 sidecar with small blocks/leaves so the
    cursor logic is exercised with real data (2 blocks x 4 leaves)."""
    from seaweedfs_tpu.ec import ShardChecksumBuilder

    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (CTX.data_shards, shard_size), dtype=np.uint8)
    parity = CpuBackend(CTX).encode(data)
    shards = np.concatenate([data, parity], axis=0)
    base = str(tmp_path / "1")
    builders = [
        ShardChecksumBuilder(block_size, leaf_size) for _ in range(CTX.total)
    ]
    for i in range(CTX.total):
        b = shards[i].tobytes()
        with open(base + CTX.to_ext(i), "wb") as f:
            f.write(b)
        builders[i].write(b)
    prot = BitrotProtection.from_builders(CTX, builders, generation=9)
    prot.save(base + ".ecsum")
    return base, shards


def flip_byte(path, offset, mask=0x01):
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ mask]))


def test_scrub_walks_leaves_and_pins_corrupt_leaf(tmp_path):
    base, shards = synth_leafy_shards(tmp_path)
    # corrupt leaf 5 (block 1, leaf 1) of shard 2
    flip_byte(base + CTX.to_ext(2), 5 * 4096 + 17)
    r = scrub_ec_volume(base, CTX, backend=CpuBackend(CTX), repair=True)
    assert r.complete and not r.refused
    # the walk pinned the rot to its leaf; with k verified sources the
    # shard is LEAF-REPAIRED in place (PR 8) — no quarantine, no
    # forensic copy, no whole-shard rebuild
    assert r.leaf_repaired == {2: [5]}, r
    assert r.checked_leaves > 0
    assert not r.quarantined and not r.rebuilt
    assert not os.path.exists(base + CTX.to_ext(2) + ".bad")
    # repair landed bit-exact
    with open(base + CTX.to_ext(2), "rb") as f:
        assert f.read() == shards[2].tobytes()


def test_scrub_budget_resumes_mid_block(tmp_path):
    """A leaf-denominated budget pause must land MID-block (cursor.leaf
    > 0 at some point) and the sliced pass must converge to the same
    verdict as an unbudgeted one."""
    base, shards = synth_leafy_shards(tmp_path)
    flip_byte(base + CTX.to_ext(3), 6 * 4096 + 3)  # block 1, leaf 2
    # 0.75 of a block per call = 3 leaves, so pauses land MID-block
    # (the budget is byte-denominated and may be fractional)
    mid_block_seen = False
    for _ in range(80):
        r = scrub_ec_volume(
            base, CTX, backend=CpuBackend(CTX), repair=True, max_blocks=0.75
        )
        cur = ScrubCursor.load(base)
        if cur is not None and cur.leaf > 0:
            mid_block_seen = True
        if r.complete:
            break
    assert r.complete and not r.refused
    assert (
        r.corrupt_leaves.get(3) == [6]
        or r.rebuilt == [3]
        or r.leaf_repaired.get(3) == [6]
    )
    with open(base + CTX.to_ext(3), "rb") as f:
        assert f.read() == shards[3].tobytes()
    assert not os.path.exists(base + ".scrubpos")
    assert mid_block_seen, "budget pause never landed mid-block"


def test_scrub_reverify_catches_new_rot_after_repair(tmp_path):
    """A shard repaired between budget slices but re-corrupted at a
    DIFFERENT leaf must not be cleared by the flagged-leaf fast path:
    clearing a verdict requires a full verify."""
    base, shards = synth_leafy_shards(tmp_path)
    flip_byte(base + CTX.to_ext(1), 0 * 4096 + 9)  # leaf 0 of shard 1
    # slice 1: walk exactly shard 0 + shard 1's first (corrupt) leaf,
    # carrying the verdict into the cursor
    r = scrub_ec_volume(
        base, CTX, backend=CpuBackend(CTX), repair=False, max_blocks=2.25
    )
    assert not r.complete
    cur = ScrubCursor.load(base)
    assert cur is not None and cur.corrupt_leaves.get(1) == [0]
    # "repair" shard 1 (restore pristine bytes), then rot a LATER leaf
    with open(base + CTX.to_ext(1), "wb") as f:
        f.write(shards[1].tobytes())
    flip_byte(base + CTX.to_ext(1), 7 * 4096 + 100)  # last leaf
    # finish the pass unbudgeted: the flagged leaf (0) now reads clean,
    # so the completion re-verify must full-scan and find leaf 7's rot
    r = scrub_ec_volume(base, CTX, backend=CpuBackend(CTX), repair=True)
    assert r.complete and not r.refused
    assert 1 in (
        set(r.corrupt_shards) | set(r.rebuilt) | set(r.leaf_repaired)
    )
    with open(base + CTX.to_ext(1), "rb") as f:
        assert f.read() == shards[1].tobytes()


def test_scrub_pause_carried_leaves_cleared_after_repair(tmp_path):
    """A shard condemned only by leaves carried from a PAUSED slice
    (never in cursor.corrupt) must still pass through the completion
    re-verify: repairing it between slices clears the verdict instead
    of quarantining a healthy shard."""
    base, shards = synth_leafy_shards(tmp_path)
    flip_byte(base + CTX.to_ext(1), 0 * 4096 + 9)  # leaf 0 of shard 1
    # budget 2.25 blocks = shard 0 (2.0) + shard 1's leaf 0, pausing
    # MID-shard-1 with the verdict only in corrupt_leaves
    r = scrub_ec_volume(
        base, CTX, backend=CpuBackend(CTX), repair=False, max_blocks=2.25
    )
    assert not r.complete
    cur = ScrubCursor.load(base)
    assert cur.corrupt_leaves.get(1) == [0] and 1 not in cur.corrupt
    # full repair lands between slices (e.g. ec.rebuild)
    with open(base + CTX.to_ext(1), "wb") as f:
        f.write(shards[1].tobytes())
    r = scrub_ec_volume(base, CTX, backend=CpuBackend(CTX), repair=True)
    assert r.complete and not r.refused
    assert 1 not in r.corrupt_shards and r.rebuilt == []
    assert not os.path.exists(base + CTX.to_ext(1) + ".bad")


def test_rebuild_noop_never_resolves_device_backend(tmp_path, monkeypatch):
    """rebuild of a healthy volume (the scrub-daemon and decode verify
    shape) is pure CRC work: it must not resolve get_backend('auto'),
    which would open the device for nothing."""
    import seaweedfs_tpu.ec.rebuild as rb

    base, _ = make_volume(tmp_path, needles=8, seed=10)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))

    def boom(*a, **kw):
        raise AssertionError("backend resolved on the no-op path")

    monkeypatch.setattr(rb, "get_backend", boom)
    assert rebuild_ec_files(base) == []  # verify-only, no device touch
    os.unlink(base + CTX.to_ext(0))
    with pytest.raises(AssertionError, match="backend resolved"):
        rebuild_ec_files(base)  # an actual target DOES resolve


def test_scrub_budget_fractional_leaves(tmp_path):
    """Leaf reads consume budget byte-proportionally: a 1-block budget
    admits a full block's worth of leaves per slice, not one leaf."""
    base, _ = synth_leafy_shards(tmp_path)
    r = scrub_ec_volume(
        base, CTX, backend=CpuBackend(CTX), repair=False, max_blocks=1,
        resumable=False,
    )
    assert not r.complete
    assert r.checked_leaves == 4  # one block's worth (4 leaves), not 1


def test_v1_sidecar_keeps_block_walk(tmp_path):
    """No leaves in the sidecar -> identical block-granular behavior
    (checked_blocks counts blocks, checked_leaves stays 0)."""
    from seaweedfs_tpu.ec import ShardChecksumBuilder

    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (CTX.data_shards, 4 * 4096), dtype=np.uint8)
    parity = CpuBackend(CTX).encode(data)
    shards = np.concatenate([data, parity], axis=0)
    base = str(tmp_path / "1")
    builders = [ShardChecksumBuilder(4096) for _ in range(CTX.total)]
    for i in range(CTX.total):
        b = shards[i].tobytes()
        with open(base + CTX.to_ext(i), "wb") as f:
            f.write(b)
        builders[i].write(b)
    BitrotProtection.from_builders(CTX, builders, generation=3).save(
        base + ".ecsum"
    )
    r = scrub_ec_volume(base, CTX, backend=CpuBackend(CTX))
    assert r.complete and r.healthy
    assert r.checked_blocks == CTX.total * 4
    assert r.checked_leaves == 0 and r.corrupt_leaves == {}


# ------------------------------------------------------------- retry sweep


def test_notifier_delivery_rides_retry_policy():
    """Transient sink failures retry on the policy schedule; permanent
    rejections do not retry; exhaustion drops."""
    from seaweedfs_tpu.filer.notification import _AsyncNotifier

    class Sink(_AsyncNotifier):
        def __init__(self, outcomes):
            self.outcomes = list(outcomes)
            self.calls = 0
            super().__init__(
                policy=RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0)
            )

        def _deliver(self, payload):
            self.calls += 1
            out = self.outcomes.pop(0)
            if isinstance(out, Exception):
                raise out
            return out

    s = Sink([RuntimeError("blip"), True])
    assert s._deliver_with_retry({"x": 1}) is True
    assert s.calls == 2
    s.close()

    s = Sink([False])  # permanent rejection: exactly one attempt
    assert s._deliver_with_retry({"x": 1}) is False
    assert s.calls == 1
    s.close()

    s = Sink([RuntimeError("a"), RuntimeError("b"), RuntimeError("c")])
    assert s._deliver_with_retry({"x": 1}) is False
    assert s.calls == 3
    s.close()


def test_upload_retries_transients_and_raises_permanent(monkeypatch):
    """Operations.upload: 5xx/transport errors re-assign + retry under
    the policy; 4xx raises immediately without another attempt."""
    import requests

    from seaweedfs_tpu.client.operations import Operations

    class FakeAssign:
        url = "localhost:1"
        fid = "1,abc"
        jwt = ""

    class R:
        def __init__(self, code):
            self.status_code = code
            self.text = "nope"

    ops = Operations.__new__(Operations)
    ops.jwt_key = ""
    assigns = []

    class FakeMaster:
        def assign(self, **kw):
            assigns.append(1)
            return FakeAssign()

    ops.master = FakeMaster()
    monkeypatch.setattr(
        Operations, "_UPLOAD_POLICY",
        RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0,
                    retry_on=(requests.RequestException, RuntimeError)),
    )

    class FlakyHttp:
        def __init__(self, codes):
            self.codes = list(codes)

        def post(self, *a, **kw):
            return R(self.codes.pop(0))

    ops._http = FlakyHttp([503, 200])
    assert ops.upload(b"data") == "1,abc"
    assert len(assigns) == 2  # re-assigned before the retry

    ops._http = FlakyHttp([403])
    assigns.clear()
    with pytest.raises(requests.HTTPError):
        ops.upload(b"data")
    assert len(assigns) == 1  # permanent: no retry

    ops._http = FlakyHttp([503, 503, 503])
    with pytest.raises(requests.HTTPError):
        ops.upload(b"data")
    assert not ops._http.codes  # all attempts consumed


def test_peer_cache_announce_backoff_policy():
    """The announce policy walks up from the normal cadence and caps at
    the peer TTL (a recovered filer is re-learned before peers expire
    this mount)."""
    from seaweedfs_tpu.mount.peer_cache import (
        ANNOUNCE_INTERVAL,
        ANNOUNCE_POLICY,
        PEER_TTL,
    )
    from seaweedfs_tpu.utils.retry import Backoff

    b = Backoff(ANNOUNCE_POLICY, rng=None)
    d1 = ANNOUNCE_POLICY.delay(1)
    assert d1 == ANNOUNCE_INTERVAL
    delays = [b.next_delay() for _ in range(6)]
    assert max(delays) <= PEER_TTL * (1 + ANNOUNCE_POLICY.jitter)
    assert delays[-1] >= delays[0]
    b.reset()
    assert b.failures == 0
