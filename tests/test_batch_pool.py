"""The process-wide pool of staged-batch matrices (ISSUE 32): the
reader of an encode or a rebuild lands every batch in a matrix that an
earlier batch of the process filled, the pool keeps no more than one
pipeline can have alive, and a matrix goes back only after `to_host`
has returned for its batch: until then the upload may read it and a
failover replays the batch from it.
"""

import os
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import native_io
from seaweedfs_tpu.ec.backend import CpuBackend, FallbackBackend, JaxBackend
from seaweedfs_tpu.ec.context import ECContext
from seaweedfs_tpu.ec.encoder import write_ec_files
from seaweedfs_tpu.ec.pipeline import BATCHES_ALIVE, QUEUE_SIZE
from seaweedfs_tpu.ec.rebuild import rebuild_ec_files
from seaweedfs_tpu.utils import trace

pytestmark = pytest.mark.skipif(
    not native_io.enabled(), reason="native core unavailable"
)

CTX = ECContext(4, 2)
BLOCK = 64 << 10  # small block: every batch of a volume below is one
LOST = (1, 4)


class Counting:
    """A pool of the process's kind that counts what it allocates."""

    def __init__(self, monkeypatch):
        self.pool = native_io.BatchPool(keep=BATCHES_ALIVE)
        self.allocated = []
        self.most_free = 0
        real_alloc, real_put = native_io.aligned_matrix, self.pool.put

        def alloc(rows, width):
            self.allocated.append((rows, width))
            return real_alloc(rows, width)

        def put(buf):
            real_put(buf)
            self.most_free = max(self.most_free, len(self.pool._free))

        monkeypatch.setattr(native_io, "aligned_matrix", alloc)
        monkeypatch.setattr(self.pool, "put", put)
        monkeypatch.setattr(native_io, "_batch_pool_singleton", self.pool)


@pytest.fixture
def counting(monkeypatch):
    monkeypatch.setenv("SEAWEED_EC_NATIVE", "1")
    return Counting(monkeypatch)


class SlowDrain(CpuBackend):
    """Carries the batch as a device backend does and computes in
    `to_host`, late: a matrix that went back to the pool before then
    would by now hold a later batch. The reader runs as far ahead of
    such a sink as the pipeline lets it, so an operation on it has
    `BATCHES_ALIVE` matrices alive, or all its batches."""

    def __init__(self, ctx, fail_on=()):
        super().__init__(ctx)
        self.fail_on, self.drained, self.overwritten = set(fail_on), 0, 0

    def apply_staged(self, coeffs, staged):
        return coeffs, staged, staged.copy()

    def encode_staged(self, staged):
        return self.apply_staged(self._ref.parity, staged)

    def to_host(self, result):
        coeffs, staged, as_read = result
        time.sleep(0.01)  # the reader is four batches ahead by now
        self.drained += 1
        if self.drained in self.fail_on:
            raise OSError("device reset mid-copy")
        self.overwritten += not np.array_equal(staged, as_read)
        return self.apply(coeffs, staged)


def make_volume(tmp_path, name, nbytes, seed=7):
    """An encoded 4+2 volume with its sidecar, and the lost shards'
    bytes as the encode wrote them."""
    base = str(tmp_path / name)
    rng = np.random.default_rng(seed)
    with open(base + ".dat", "wb") as f:
        f.write(rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes())
    encode(base, SlowDrain(CTX)).save(base + ".ecsum")
    return base, {i: open(base + CTX.to_ext(i), "rb").read() for i in LOST}


def encode(base, backend=None):
    return write_ec_files(
        base, CTX, backend or CpuBackend(CTX),
        large_block_size=1 << 20, small_block_size=BLOCK,
    )


def lose_and_rebuild(base, want, backend=None, batch_size=48 << 10):
    for i in want:
        os.unlink(base + CTX.to_ext(i))
    got = rebuild_ec_files(
        base, CTX, backend=backend or CpuBackend(CTX), batch_size=batch_size
    )
    assert sorted(got) == sorted(want)
    for i, data in want.items():
        assert open(base + CTX.to_ext(i), "rb").read() == data, i


def test_the_bound_is_what_one_pipeline_can_have_alive():
    # a batch with each of the three stages, and the two queues full
    assert BATCHES_ALIVE == 2 * QUEUE_SIZE + 3
    assert native_io.batch_pool().keep == BATCHES_ALIVE
    assert native_io.batch_pool() is native_io.batch_pool()


def test_the_second_rebuild_of_a_process_allocates_no_matrix(tmp_path, counting):
    base, want = make_volume(tmp_path, "v", (1 << 20) + 4321)
    counting.allocated.clear()
    lose_and_rebuild(base, want, backend=SlowDrain(CTX))
    # shards of 320 KiB in batches of 48 KiB: six full and a tail
    assert sorted(counting.allocated) == [(4, 32 << 10)] + 6 * [(4, 48 << 10)]
    counting.allocated.clear()
    lose_and_rebuild(base, want)
    assert counting.allocated == []


def test_the_second_encode_of_a_process_allocates_no_matrix(tmp_path, counting):
    base, _want = make_volume(tmp_path, "v", (1 << 20) + 4321)
    assert counting.allocated == 5 * [(4, BLOCK)]  # five rows of small blocks
    before = {i: open(base + CTX.to_ext(i), "rb").read() for i in range(CTX.total)}
    counting.allocated.clear()
    prot = encode(base)
    assert counting.allocated == []
    for i, data in before.items():
        assert open(base + CTX.to_ext(i), "rb").read() == data, i
    assert prot.shard_sizes == [len(before[i]) for i in range(CTX.total)]


def test_an_armed_second_rebuild_counts_every_byte_read_as_reused(tmp_path, counting):
    base, want = make_volume(tmp_path, "v", (1 << 20) + 4321)
    lose_and_rebuild(base, want, backend=SlowDrain(CTX))
    trace.configure(enabled=True, ring_size=64, slow_op_s=0.0)
    trace.reset()
    try:
        lose_and_rebuild(base, want)
        encode(base)
        docs = {d["op"]: d for d in trace.traces()}
    finally:
        trace.configure(enabled=False, slow_op_s=0.0)
        trace.reset()
    shard = len(want[LOST[0]])
    attrs = docs["ec.rebuild"]["attrs"]
    assert attrs["read_bytes"] == CTX.data_shards * shard
    assert attrs["read_reused_bytes"] == attrs["read_bytes"]
    # the encode's batches are (4, 64 KiB), a class of their own: the
    # rebuild's seven pushed out what the first encode had left
    attrs = docs["ec.encode"]["attrs"]
    assert attrs["read_bytes"] == CTX.data_shards * shard
    assert attrs.get("read_reused_bytes", 0) < attrs["read_bytes"]


def test_a_python_plane_rebuild_counts_its_reads_and_reuses_nothing(
    tmp_path, counting, monkeypatch
):
    base, want = make_volume(tmp_path, "v", 1 << 20)
    monkeypatch.setenv("SEAWEED_EC_NATIVE", "0")
    counting.allocated.clear()
    trace.configure(enabled=True, ring_size=64, slow_op_s=0.0)
    trace.reset()
    try:
        lose_and_rebuild(base, want)
        attrs = next(d for d in trace.traces() if d["op"] == "ec.rebuild")["attrs"]
    finally:
        trace.configure(enabled=False, slow_op_s=0.0)
        trace.reset()
    assert counting.allocated == [] and counting.most_free <= BATCHES_ALIVE
    assert attrs["read_bytes"] == CTX.data_shards * len(want[LOST[0]])
    assert "read_reused_bytes" not in attrs


def test_the_bound_holds_under_two_concurrent_rebuilds(tmp_path, counting):
    vols = [make_volume(tmp_path, f"v{n}", (2 << 20) + 99 * n, seed=n) for n in (1, 2)]
    errors = []

    def work(base, want):
        try:
            for _ in range(3):
                lose_and_rebuild(base, want, batch_size=16 << 10)
        except BaseException as e:  # noqa: BLE001 - raised below
            errors.append(e)

    threads = [threading.Thread(target=work, args=v, daemon=True) for v in vols]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert 0 < counting.most_free <= BATCHES_ALIVE
    assert len(counting.pool._free) <= BATCHES_ALIVE


@pytest.mark.parametrize("shape, other", [
    pytest.param((10, 4096), (10, 2048), id="a_tail_is_not_a_slice_of_a_full_batch"),
    pytest.param((10, 4096), (4, 4096), id="4p2_is_not_rows_of_10p4"),
    pytest.param((16, 1024), (4, 4096), id="the_same_bytes_in_another_shape"),
])
def test_a_shape_is_a_class_of_its_own(shape, other):
    pool = native_io.BatchPool(keep=BATCHES_ALIVE)
    a, held = pool.get(*shape)
    assert not held and a.shape == shape and a.ctypes.data % 4096 == 0
    pool.put(a)
    b, held = pool.get(*other)
    assert not held and b.shape == other and b.flags.c_contiguous
    assert not np.shares_memory(a, b)
    again, held = pool.get(*shape)
    assert held and again is a


def test_a_put_over_the_bound_drops_what_has_lain_longest():
    pool = native_io.BatchPool(keep=3)
    old = [pool.get(2, 64)[0] for _ in range(3)]
    for m in old:
        pool.put(m)
    new = [pool.get(2, 128)[0] for _ in range(2)]
    for m in new:
        pool.put(m)
    assert len(pool._free) == 3
    # the two that lay longest went; the running shape is all there
    assert pool.get(2, 128)[0] is new[1] and pool.get(2, 128)[0] is new[0]
    assert pool.get(2, 64)[0] is old[2]
    assert pool.get(2, 64)[1] is False


def test_a_matrix_is_not_handed_out_again_before_to_host_has_returned(
    tmp_path, counting
):
    base, want = make_volume(tmp_path, "v", 2 << 20)
    be = SlowDrain(CTX)
    for _ in range(2):
        lose_and_rebuild(base, want, backend=be, batch_size=16 << 10)
    assert be.drained == 2 * 32 and be.overwritten == 0
    # and the pool was in use all the while: one pipeline's worth allocated
    assert len([s for s in counting.allocated if s == (4, 16 << 10)]) <= BATCHES_ALIVE


def test_a_mid_batch_failover_replays_from_the_carried_matrix(tmp_path, counting):
    """The device dies between dispatch and drain: `FallbackBackend`
    recomputes the batch on the CPU from the host copy it carries,
    which is the pool's matrix."""
    base, want = make_volume(tmp_path, "v", 2 << 20)
    fb = FallbackBackend(JaxBackend(CTX, impl="xla", n_devices=1), CpuBackend(CTX))
    real, calls = fb.primary.to_host, []

    def dying(handle):
        calls.append(1)
        if len(calls) in (3, 4, 9):
            time.sleep(0.02)
            raise OSError("device reset mid-copy")
        return real(handle)

    fb.primary.to_host = dying
    counting.allocated.clear()
    for _ in range(2):
        lose_and_rebuild(base, want, backend=fb, batch_size=16 << 10)
    assert fb.fallback_batches >= 3
    assert 0 < len(counting.allocated) <= BATCHES_ALIVE


def test_an_aborted_pipeline_leaves_the_pool_usable(tmp_path, counting):
    base, want = make_volume(tmp_path, "v", 2 << 20)
    for i in want:
        os.unlink(base + CTX.to_ext(i))
    with pytest.raises(OSError, match="device reset"):
        rebuild_ec_files(
            base, CTX, backend=SlowDrain(CTX, fail_on=[5]), batch_size=16 << 10
        )
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".rebuilding")]
    assert len(counting.pool._free) <= BATCHES_ALIVE
    # what the aborted batches held is the collector's; the rest serves on
    for i in want:
        assert not os.path.exists(base + CTX.to_ext(i))
    got = rebuild_ec_files(base, CTX, backend=SlowDrain(CTX), batch_size=16 << 10)
    assert sorted(got) == sorted(want)
    for i, data in want.items():
        assert open(base + CTX.to_ext(i), "rb").read() == data, i
    counting.allocated.clear()
    lose_and_rebuild(base, want, batch_size=16 << 10)
    assert counting.allocated == []


def test_a_short_source_starts_its_retry_from_the_pool(tmp_path, counting):
    """A source that cannot be read is excluded and the rebuild starts
    over: the second attempt takes its matrices from the pool like a
    first one."""
    base, want = make_volume(tmp_path, "v", 2 << 20)
    lose_and_rebuild(base, want, backend=SlowDrain(CTX), batch_size=16 << 10)
    good = open(base + CTX.to_ext(0), "rb").read()
    with open(base + CTX.to_ext(0), "r+b") as f:
        f.seek(300_000)
        f.write(b"\xba\xad")
    os.unlink(base + CTX.to_ext(LOST[0]))
    counting.allocated.clear()
    got = rebuild_ec_files(base, CTX, backend=CpuBackend(CTX), batch_size=16 << 10)
    assert set(got) == {0, LOST[0]}
    assert open(base + CTX.to_ext(0), "rb").read() == good
    assert open(base + CTX.to_ext(LOST[0]), "rb").read() == want[LOST[0]]
    assert counting.allocated == []
