"""How a device apply's result comes home (PR 30): every batch crosses
the host link as int32 words of four consecutive bytes, the copy home
is asked for where the apply is launched, and the host sees the same
uint8 rows as before, as a view of what was fetched.

Bit-identity with the CPU backend is the load-bearing property: for
every count of rows out, for widths that do and do not fill a word or
a tile, through every entry of the seam and on every device backend.
"""

import os

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import (
    CpuBackend,
    ECContext,
    FallbackBackend,
    JaxBackend,
    ec_encode_volume,
    rebuild_ec_files,
)
from seaweedfs_tpu.ec.backend import _decode_coeffs
from seaweedfs_tpu.ec.pipeline import run_staged_apply
from seaweedfs_tpu.utils import metrics as M
from seaweedfs_tpu.utils import trace

from test_ec_staged_apply import make_volume

CTX = ECContext(10, 4)
CPU = CpuBackend(CTX)
TILE = 128  # the interpreted kernel's tile, in lanes


def make_backend(kind):
    if kind == "xla":
        return JaxBackend(CTX, impl="xla", n_devices=1)
    if kind == "mesh":
        return JaxBackend(CTX, impl="xla")  # conftest forces 8 virtual devices
    # "pallas_mesh": the kernel inside shard_map over those devices, what
    # a four-chip host builds by itself
    be = JaxBackend(
        CTX, impl="pallas", interpret=True,
        n_devices=None if kind == "pallas_mesh" else 1,
    )
    be._rs.tile_n = TILE
    return be


@pytest.fixture(scope="module", params=["xla", "pallas", "pallas_mesh", "mesh"])
def backend(request):
    return make_backend(request.param)


# a word and a tile of words filled and not: 4 | n, 4 * TILE | n, neither
WIDTHS = [1, 3, 4, 6, 512, 515, 4 * TILE, 4 * TILE * 3 + 2, 4099]


def batch(width, seed=0):
    return np.random.default_rng([seed, width]).integers(
        0, 256, (CTX.data_shards, width), dtype=np.uint8
    )


def coeffs(m_out):
    return np.random.default_rng(m_out).integers(
        0, 256, (m_out, CTX.data_shards), dtype=np.uint8
    )


@pytest.mark.parametrize("m_out", [1, 2, 3, 4, 14])
def test_apply_and_apply_staged_equal_the_cpu_for_every_row_count(backend, m_out):
    c = coeffs(m_out)
    for width in WIDTHS:
        data = batch(width)
        want = CPU.apply(c, data)
        for got in (
            backend.apply(c, data),
            backend.to_host(backend.apply_staged(c, backend.to_device(data))),
        ):
            assert got.dtype == np.uint8 and got.shape == (m_out, width)
            assert np.array_equal(got, want), (m_out, width)


def test_encode_and_encode_staged_equal_the_cpu(backend):
    for width in WIDTHS:
        data = batch(width, seed=1)
        want = CPU.encode(data)
        assert np.array_equal(backend.encode(data), want), width
        got = backend.to_host(backend.encode_staged(backend.to_device(data)))
        assert got.dtype == np.uint8 and np.array_equal(got, want), width


@pytest.mark.parametrize("lost", [(3,), (3, 11), (0, 5, 12), (1, 4, 8, 13)])
def test_reconstruct_equals_the_cpu(backend, lost):
    for width in (6, 515, 4 * TILE):
        data = batch(width, seed=2)
        full = np.concatenate([data, CPU.encode(data)])
        left = {i: full[i] for i in range(CTX.total) if i not in lost}
        got = backend.reconstruct(left, want=list(lost))
        assert sorted(got) == sorted(lost)
        for i in lost:
            assert np.array_equal(got[i], full[i]), (lost, width, i)


@pytest.mark.parametrize("m_out", [1, 2, 4])
@pytest.mark.parametrize("kind", ["xla", "pallas"])
def test_the_host_rows_are_a_view_of_what_was_fetched(kind, m_out):
    """No second copy on the host: the uint8 rows `to_host` returns are
    C-contiguous and lie in the very buffer the fetch filled (numpy's
    view of the device array keeps it, so fetching the handle's words
    again gives the same memory)."""
    be = make_backend(kind)
    handle = be.apply_staged(coeffs(m_out), be.to_device(batch(4 * TILE)))
    out = be.to_host(handle)
    assert out.dtype == np.uint8 and out.flags.c_contiguous
    words, n = handle
    assert n == 4 * TILE and words.dtype == np.int32
    assert words.shape == (m_out, TILE)
    assert np.shares_memory(out, np.asarray(words))
    # what the sink does with it finds nothing to copy
    assert np.ascontiguousarray(out, dtype=np.uint8) is out
    # a width that does not fill its last word: the pad is cut by a slice
    odd = be.to_host(be.apply_staged(coeffs(m_out), be.to_device(batch(515))))
    assert odd.shape == (m_out, 515) and odd.base is not None


class _Asked:
    """A device result that says whether the copy home was asked for."""

    def __init__(self, arr, log):
        self._arr, self._log = arr, log

    def copy_to_host_async(self):
        self._log.append("asked")
        self._arr.copy_to_host_async()

    def __getattr__(self, name):
        return getattr(self._arr, name)

    def __array__(self, dtype=None, copy=None):
        self._log.append("fetched")
        return np.asarray(self._arr, dtype=dtype)


def test_the_copy_home_is_asked_for_at_launch_not_at_the_fetch(monkeypatch):
    be = make_backend("xla")
    log = []
    for name in ("apply", "encode"):
        real = getattr(be._rs, name)
        monkeypatch.setattr(
            be._rs, name, lambda *a, _real=real: _Asked(_real(*a), log)
        )
    data = batch(512)
    handle = be.apply_staged(coeffs(2), be.to_device(data))
    assert log == ["asked"]
    assert np.array_equal(be.to_host(handle), CPU.apply(coeffs(2), data))
    assert log == ["asked", "fetched"]
    handle = be.encode_staged(be.to_device(data))
    assert log == ["asked", "fetched", "asked"]
    assert np.array_equal(be.to_host(handle), CPU.encode(data))


def _lose_two_and_rebuild(tmp_path, backend):
    base, _payloads = make_volume(tmp_path, needles=40)
    ec_encode_volume(base, CTX)
    want = {i: open(base + CTX.to_ext(i), "rb").read() for i in (0, 3)}
    for i in (0, 3):
        os.unlink(base + CTX.to_ext(i))
    assert rebuild_ec_files(base, CTX, backend=backend) == [0, 3]
    for i in (0, 3):
        assert open(base + CTX.to_ext(i), "rb").read() == want[i], i


def _dense_total():
    text = M.REGISTRY.render().decode()
    line = next(
        (ln for ln in text.splitlines()
         if ln.startswith('sw_ec_d2h_dense_bytes_total{op="ec.rebuild"}')),
        None,
    )
    return float(line.split()[-1]) if line else 0.0


def test_an_armed_rebuild_counts_every_fetched_byte_as_dense(tmp_path):
    trace.configure(enabled=True, ring_size=64, slow_op_s=0.0)
    trace.reset()
    before = _dense_total()
    try:
        _lose_two_and_rebuild(tmp_path, JaxBackend(CTX, n_devices=1))
        doc = next(d for d in trace.traces() if d["op"] == "ec.rebuild")
    finally:
        trace.configure(enabled=False, slow_op_s=0.0)
        trace.reset()
    attrs = doc["attrs"]
    assert attrs["d2h_bytes"] > 0
    assert attrs["d2h_dense_bytes"] == attrs["d2h_bytes"]
    assert attrs["h2d_bytes"] == 5 * attrs["d2h_bytes"]  # 10 rows up, 2 home
    assert _dense_total() - before == attrs["d2h_dense_bytes"]
    # the sink's own pass finds nothing to copy: no stage but the seam's
    assert "device_drain.d2h" in doc["stages"]


def test_a_disarmed_rebuild_counts_nothing(tmp_path):
    assert not trace.armed
    before = _dense_total()
    _lose_two_and_rebuild(tmp_path, JaxBackend(CTX, n_devices=1))
    assert _dense_total() == before
    assert not [d for d in trace.traces() if d["op"] == "ec.rebuild"]


def test_a_fetch_that_fails_after_the_early_copy_is_replayed_on_the_cpu(
    tmp_path, monkeypatch
):
    """`ec.device.kernel_fetch` fires in `to_host`, after the launch has
    asked for the copy home: the batch in flight is rebuilt on the CPU
    from the host copy its handle carries, and the rebuilt shards are
    the bytes they were. (An armed registry sends `rebuild_ec_files`
    down its byte path, so the rebuild's own pipeline is driven here.)"""
    base, _payloads = make_volume(tmp_path, needles=40)
    ec_encode_volume(base, CTX, backend=CPU)
    shards = [
        np.fromfile(base + CTX.to_ext(i), dtype=np.uint8) for i in range(CTX.total)
    ]
    lost = (0, 3)
    src = tuple(i for i in range(CTX.total) if i not in lost)[: CTX.data_shards]
    fb = FallbackBackend(JaxBackend(CTX, impl="xla", n_devices=1), CpuBackend(CTX))
    log = []
    real = fb.primary._rs.apply
    monkeypatch.setattr(
        fb.primary._rs, "apply", lambda *a: _Asked(real(*a), log)
    )
    size, step = len(shards[0]), 10_001  # ragged: words and a tail are padded

    def produce():
        for off in range(0, size, step):
            yield off, np.stack([shards[i][off : off + step] for i in src])

    rebuilt = {i: np.zeros(size, np.uint8) for i in lost}

    def consume(off, out):
        for row, i in zip(out, lost):
            rebuilt[i][off : off + len(row)] = row

    with faults.injected(
        "ec.device.kernel_fetch", faults.io_error("device reset mid-copy"),
        when=faults.nth_call(2), count=1,
    ) as h:
        run_staged_apply(
            fb, _decode_coeffs(fb.matrix, CTX.data_shards, lost, src),
            produce, consume,
        )
    assert h.fired == 1 and fb.fallback_batches == 1
    n_batches = -(-size // step)
    assert n_batches > 2 and log.count("asked") == n_batches
    assert log.count("fetched") == n_batches - 1  # the failed one never was
    for i in lost:
        assert np.array_equal(rebuilt[i], shards[i]), i
