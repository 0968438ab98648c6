"""Zero-copy native data plane (ISSUE 10): bit-identity vs the Python
source/sink, fault routing, torn-write crash consistency, skip-clean
fallback, and the build-and-symbol tier-1 gate for native/.
"""

import ctypes
import os
import subprocess
import sys

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import native_io
from seaweedfs_tpu.ec.backend import CpuBackend
from seaweedfs_tpu.ec.bitrot import BitrotProtection, ShardChecksumBuilder
from seaweedfs_tpu.ec.context import ECContext, ECError
from seaweedfs_tpu.ec.encoder import write_ec_files
from seaweedfs_tpu.ec.pipeline import (
    FusedShardSink,
    PyShardSink,
    make_shard_sink,
)
from seaweedfs_tpu.ec.rebuild import rebuild_ec_files

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE_DIR = os.path.join(REPO, "native")

# The new C ABI this PR introduces; a stale .so missing any of these
# must FAIL tests (silent loss of the whole native plane), not skip.
NEW_SYMBOLS = [
    "sn_batch_pread",
    "sn_fadvise_willneed",
    "sn_crc32c_combine",
    "sn_sink_create",
    "sn_sink_append",
    "sn_sink_finish",
    "sn_sink_destroy",
    # ISSUE 12: network byte plane + O_DIRECT sink observability. Same
    # contract — a stale .so missing these silently disables the whole
    # native plane (the bindings in utils/native.py resolve at import),
    # so the gate fails loudly here instead.
    "sn_send_file",
    "sn_sendv",
    "sn_recv_into",
    "sn_sink_direct_flags",
    # ISSUE 13: env-tunable overlapped-recv core gate probe
    "sn_recv_overlap_active",
]


# --------------------------------------------------------------- tier-1
# build-and-symbol gate


def test_native_builds_and_new_symbols_resolve():
    """`make -C native/` must succeed and the freshly built .so must
    export the data-plane ABI — a host without the toolchain, or a
    stale library, fails here instead of silently running pure
    Python."""
    proc = subprocess.run(
        ["make", "-s", "-C", NATIVE_DIR],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, (
        f"native build failed:\n{proc.stderr[-2000:]}"
    )
    lib = ctypes.CDLL(os.path.join(NATIVE_DIR, "libseaweed_native.so"))
    for sym in NEW_SYMBOLS + ["sn_crc32c", "sn_rs_apply", "sn_shard_append"]:
        assert getattr(lib, sym, None) is not None, f"missing symbol {sym}"


def test_import_failure_is_importerror(tmp_path):
    """Load-contract satellite: a failing `make` (no toolchain / broken
    sources) must surface as ImportError — the only exception callers
    are documented to tolerate — never CalledProcessError."""
    bad = tmp_path / "native"
    bad.mkdir()
    (bad / "Makefile").write_text("all:\n\tfalse\n")
    code = (
        "import sys\n"
        "try:\n"
        "    import seaweedfs_tpu.utils.native\n"
        "except ImportError:\n"
        "    sys.exit(0)\n"
        "except BaseException as e:\n"
        "    print('WRONG exception:', type(e).__name__)\n"
        "    sys.exit(2)\n"
        "sys.exit(3)  # import unexpectedly succeeded\n"
    )
    env = dict(os.environ, SEAWEED_NATIVE_DIR=str(bad))
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        cwd=REPO,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_stale_detects_any_native_source(tmp_path, monkeypatch):
    """_stale derives the source list from the directory, so a NEW
    source file (not just seaweed_native.cpp) triggers a rebuild."""
    from seaweedfs_tpu.utils import native

    d = tmp_path / "native"
    d.mkdir()
    (d / "Makefile").write_text("all:\n")
    so = d / "libseaweed_native.so"
    so.write_bytes(b"x")
    monkeypatch.setattr(native, "_NATIVE_DIR", str(d))
    monkeypatch.setattr(native, "_SO_PATH", str(so))
    assert not native._stale()
    extra = d / "new_kernel.cpp"
    extra.write_text("// new source")
    os.utime(extra, (os.path.getmtime(so) + 5, os.path.getmtime(so) + 5))
    assert native._stale()


# ------------------------------------------------------- bit identity

CTX64 = ECContext(4, 2)


def _make_dat(tmp_path, name, nbytes, seed=7):
    rng = np.random.default_rng(seed)
    base = str(tmp_path / name)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    return base


@pytest.mark.parametrize("batch_size", [
    pytest.param(16 << 20, id="a_batch_a_block"),
    # blocks of 1 MiB in batches of 300,001 bytes: three full and a
    # ragged tail a block, two width classes of the batch pool
    pytest.param(300_001, id="ragged_batches"),
])
@pytest.mark.parametrize("leaf_size", [0, 64 * 1024])
@pytest.mark.parametrize("tail", [0, 12345])
def test_encode_native_vs_python_bit_identical(
    tmp_path, monkeypatch, leaf_size, tail, batch_size
):
    """Same .dat, native plane vs SEAWEED_EC_NATIVE=0: shard bytes,
    sizes, block CRCs and (v2) leaf CRCs must match bit for bit —
    across ragged tails and both sidecar versions."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    nbytes = (6 << 20) + tail
    base_n = _make_dat(tmp_path, "vn", nbytes)
    base_p = _make_dat(tmp_path, "vp", nbytes)
    be = CpuBackend(CTX64)

    monkeypatch.setenv("SEAWEED_EC_NATIVE", "1")
    prot_n = write_ec_files(
        base_n, CTX64, be, batch_size=batch_size, leaf_size=leaf_size
    )
    monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
    prot_p = write_ec_files(
        base_p, CTX64, be, batch_size=batch_size, leaf_size=leaf_size
    )

    assert prot_n.shard_sizes == prot_p.shard_sizes
    assert prot_n.shard_crcs == prot_p.shard_crcs
    assert prot_n.shard_leaf_crcs == prot_p.shard_leaf_crcs
    assert prot_n.leaf_size == prot_p.leaf_size == leaf_size
    for i in range(CTX64.total):
        a = open(base_n + CTX64.to_ext(i), "rb").read()
        b = open(base_p + CTX64.to_ext(i), "rb").read()
        assert a == b, f"shard {i} differs"


@pytest.mark.parametrize("batch_size", [
    pytest.param(16 << 20, id="one_batch"),
    # a 1 MiB shard in batches that do and do not divide it: the pool's
    # full-width class, and a tail that is none, 3 bytes short or ragged
    pytest.param(256 << 10, id="no_tail"),
    pytest.param((256 << 10) + 1, id="tail_3_short"),
    pytest.param(300_001, id="ragged_tail"),
])
def test_rebuild_native_vs_python_bit_identical(tmp_path, monkeypatch, batch_size):
    """Both planes twice over, the native one from the process-wide
    batch pool (its second pass lands in matrices the first filled)."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    base = _make_dat(tmp_path, "v", (3 << 20) + 999)
    be = CpuBackend(CTX64)
    prot = write_ec_files(base, CTX64, be)
    prot.save(base + ".ecsum")
    originals = {
        i: open(base + CTX64.to_ext(i), "rb").read() for i in (1, 5)
    }
    for env in ("1", "0", "1"):
        monkeypatch.setenv("SEAWEED_EC_NATIVE", env)
        for i in originals:
            os.unlink(base + CTX64.to_ext(i))
        got = rebuild_ec_files(base, CTX64, backend=be, batch_size=batch_size)
        assert sorted(got) == sorted(originals)
        for i, want in originals.items():
            assert open(base + CTX64.to_ext(i), "rb").read() == want


def test_rebuild_native_inline_crc_excludes_rotten_source(
    tmp_path, monkeypatch
):
    """The fused read+CRC (native roller) must drive the same
    verify-and-exclude envelope as the Python _BlockCrcRoller: a
    bit-flipped source is confirmed from disk, reclassified, and the
    rebuild succeeds without it."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    monkeypatch.setenv("SEAWEED_EC_NATIVE", "1")
    base = _make_dat(tmp_path, "v", 2 << 20)
    be = CpuBackend(CTX64)
    prot = write_ec_files(base, CTX64, be)
    prot.save(base + ".ecsum")
    good = open(base + CTX64.to_ext(0), "rb").read()
    with open(base + CTX64.to_ext(0), "r+b") as f:
        f.seek(4321)
        f.write(b"\xba\xad")
    os.unlink(base + CTX64.to_ext(5))
    got = rebuild_ec_files(base, CTX64, backend=be)
    assert set(got) >= {0, 5}
    assert open(base + CTX64.to_ext(0), "rb").read() == good


def test_native_sink_preserves_file_position(tmp_path):
    """The stateful sink pwrite(2)s at tracked offsets: the Python file
    object's position must stay untouched (flush/fsync/close safe)."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    files = [
        open(tmp_path / f"s{i}", "wb", buffering=0) for i in range(3)
    ]
    try:
        sink = FusedShardSink(files, block_size=4096, leaf_size=1024)
        rows = np.random.default_rng(1).integers(
            0, 256, (3, 5000), np.uint8
        )
        sink.append_rows(list(rows))
        sink.append_rows(list(rows))
        assert [f.tell() for f in files] == [0, 0, 0]
        assert sink.sizes == [10000] * 3
        sink._finish()
        for i, f in enumerate(files):
            f.close()
            got = open(tmp_path / f"s{i}", "rb").read()
            assert got == rows[i].tobytes() * 2
        files = []
    finally:
        for f in files:
            f.close()


def test_native_sink_dual_level_matches_builder(tmp_path):
    """One-pass leaf rolling + block folding == the two-level
    ShardChecksumBuilder, including partial-tail granules."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    f = open(tmp_path / "s0", "wb", buffering=0)
    try:
        sink = FusedShardSink([f], block_size=8192, leaf_size=2048)
        builder = ShardChecksumBuilder(8192, 2048)
        rng = np.random.default_rng(2)
        for width in (8192, 3000, 2048, 57):
            row = rng.integers(0, 256, width, np.uint8)
            sink.append_rows([row])
            builder.write(row.tobytes())
        assert sink.block_crcs() == [builder.finish()]
        assert sink.leaf_crcs() == [builder.finish_leaves()]
    finally:
        f.close()


# ---------------------------------------------------- fault machinery


def test_armed_registry_routes_python_plane(tmp_path):
    """Byte-mutating fault points need materialized bytes: with the
    registry ARMED the encode produce and the shard sink must take the
    Python plane — and the output stays bit-identical to the native
    run (the fallback IS the reference implementation)."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    base_n = _make_dat(tmp_path, "vn", 1 << 20)
    base_c = _make_dat(tmp_path, "vc", 1 << 20)
    be = CpuBackend(CTX64)
    write_ec_files(base_n, CTX64, be)

    faults.inject("test.native_plane.noop", lambda ctx: None)  # arm only
    try:
        assert faults.active()
        assert isinstance(
            make_shard_sink(
                [open(os.devnull, "wb")], prefer_fused=not faults.active()
            ),
            PyShardSink,
        )
        write_ec_files(base_c, CTX64, be)
    finally:
        faults.clear()
    for i in range(CTX64.total):
        assert (
            open(base_n + CTX64.to_ext(i), "rb").read()
            == open(base_c + CTX64.to_ext(i), "rb").read()
        )


def test_encode_fault_points_fire_on_native_path(tmp_path):
    """PR 1 crash-window fire points still run on the native plane:
    a raising ec.encode.before_fsync aborts the encode (shards present,
    no sidecar published by write_ec_files' caller)."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    base = _make_dat(tmp_path, "v", 1 << 20)

    class Boom(RuntimeError):
        pass

    def handler(ctx):
        raise Boom("crash window")

    faults.inject("ec.encode.before_fsync", handler)
    try:
        with pytest.raises(Boom):
            write_ec_files(base, CTX64, CpuBackend(CTX64))
    finally:
        faults.clear()


def test_torn_write_through_native_sink_is_caught(tmp_path, monkeypatch):
    """Crash-consistency: shards written by the native sink, then a
    torn write (truncated tail — the mid-pwrite power-cut shape).
    Rebuild's size-vs-sidecar gate must reclassify and regenerate the
    torn shard bit-exactly."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    monkeypatch.setenv("SEAWEED_EC_NATIVE", "1")
    base = _make_dat(tmp_path, "v", 2 << 20)
    be = CpuBackend(CTX64)
    prot = write_ec_files(base, CTX64, be)
    prot.save(base + ".ecsum")
    shard = base + CTX64.to_ext(2)
    good = open(shard, "rb").read()
    os.truncate(shard, len(good) - 1000)
    got = rebuild_ec_files(base, CTX64, backend=be)
    assert 2 in got
    assert open(shard, "rb").read() == good


def test_native_sink_write_failure_fails_closed(tmp_path):
    """A dead fd mid-stream surfaces as an error (never a silent
    truncated-success): append_rows raises and no CRCs are minted for
    the failed batch."""
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    f = open(tmp_path / "s0", "wb", buffering=0)
    sink = FusedShardSink([f], block_size=4096)
    row = np.zeros(4096, np.uint8)
    sink.append_rows([row])
    f.close()  # the "crash"
    with pytest.raises(OSError):
        sink.append_rows([row])


# ------------------------------------------------------- skip-clean


def test_encode_skip_clean_without_native(tmp_path, monkeypatch):
    """With the .so unavailable (import raises), the whole byte path
    must run pure Python and still produce a correct volume — the
    native core is an accelerator, not a dependency."""
    base = _make_dat(tmp_path, "v", (1 << 20) + 777)
    # Simulate an unavailable native core for FRESH imports: drop the
    # already-bound package attribute AND poison sys.modules (a None
    # entry makes `import seaweedfs_tpu.utils.native` raise ImportError).
    import seaweedfs_tpu.utils as _utils

    monkeypatch.delattr(_utils, "native", raising=False)
    monkeypatch.setitem(sys.modules, "seaweedfs_tpu.utils.native", None)
    assert not native_io.enabled()
    sink = make_shard_sink([open(os.devnull, "wb")])
    assert isinstance(sink, PyShardSink)
    be = CpuBackend(CTX64)
    prot = write_ec_files(base, CTX64, be)
    prot.save(base + ".ecsum")
    assert not prot.verify_shard_file(base + CTX64.to_ext(0), 0)
    # degraded-path read helpers fall back too
    buf = np.empty(1024, np.uint8)
    fd = os.open(base + CTX64.to_ext(0), os.O_RDONLY)
    try:
        native_io.read_exact_into(fd, buf, 0)
    finally:
        os.close(fd)
    assert buf.tobytes() == open(base + CTX64.to_ext(0), "rb").read(1024)


# ------------------------------------------------- read-source pieces


def test_batch_pread_fused_crc_matches_python_roller(tmp_path):
    if not native_io.enabled():
        pytest.skip("native core unavailable")
    from seaweedfs_tpu.ec.rebuild import _BlockCrcRoller

    rng = np.random.default_rng(3)
    n = (1 << 18) + 333
    paths = []
    for i in range(3):
        p = tmp_path / f"f{i}"
        p.write_bytes(rng.integers(0, 256, n, np.uint8).tobytes())
        paths.append(p)
    fds = [os.open(p, os.O_RDONLY) for p in paths]
    try:
        block = 1 << 16
        state = np.zeros(3, np.uint32)
        filled = np.zeros(3, np.uint64)
        lists = [[] for _ in range(3)]
        rollers = [_BlockCrcRoller(block) for _ in range(3)]
        batch = 50_000
        out_crcs = np.empty((3, batch // block + 2), np.uint32)
        out_counts = np.empty(3, np.int32)
        for off in range(0, n, batch):
            width = min(batch, n - off)
            buf = np.empty((3, width), np.uint8)
            native_io.read_batch(
                fds, [off] * 3, buf, pad_eof=False, granule=block,
                crc_state=state, filled_state=filled,
                out_crcs=out_crcs, out_counts=out_counts,
            )
            for r in range(3):
                lists[r].extend(
                    int(x) for x in out_crcs[r, : out_counts[r]]
                )
                rollers[r].update(buf[r])
        for r in range(3):
            if filled[r]:
                lists[r].append(int(state[r]))
            assert lists[r] == rollers[r].finish()
    finally:
        for fd in fds:
            os.close(fd)


def test_buffer_pool_reuses_by_width():
    pool = native_io.BufferPool(rows=4)
    a = pool.get(1024)
    addr = a.ctypes.data
    assert addr % 4096 == 0
    pool.put(a)
    b = pool.get(1024)
    assert b.ctypes.data == addr  # same matrix back
    c = pool.get(2048)
    assert c.shape == (4, 2048) and c.ctypes.data % 4096 == 0
