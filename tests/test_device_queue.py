"""PR 4 device-queue scheduler tests: the shared per-chip priority
scheduler (ec/device_queue.py) multiplexing encode / degraded-read /
rebuild / scrub streams, plus the store-level shared interval cache.

Load-bearing properties:

- bit-identity: every stream's output through the queue equals the
  synchronous apply, on every backend family, under interleaving;
- fairness: a saturating recovery stream cannot starve foreground
  (bounded foreground wait), and foreground cannot starve recovery
  below its configured minimum share (no starvation either way);
- fault isolation: a mid-stream device death replays only the victim
  stream's in-flight batches on CPU; other streams keep the device
  until the shared breaker trips; a dying stream never leaks window
  slots;
- one byte budget: all EcVolumes of a Store share one interval cache
  with volume-namespaced invalidation.
"""

import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import (
    CpuBackend,
    ECContext,
    ECError,
    FallbackBackend,
    JaxBackend,
    ec_encode_volume,
)
from seaweedfs_tpu.ec.backend import _decode_coeffs
from seaweedfs_tpu.ec.device_queue import (
    DEFAULT_SHARES,
    DeviceQueue,
    configure,
    for_backend,
    stats_snapshot,
)
from seaweedfs_tpu.ec.pipeline import run_staged_apply
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils.retry import CircuitBreaker

CTX = ECContext(10, 4)
K = CTX.data_shards


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


def staged_through_queue(be, queue, coeffs, data, priority, batch=4096):
    """Run `data` through run_staged_apply on `queue`; returns output."""
    total = data.shape[1]
    out = np.zeros((coeffs.shape[0], total), dtype=np.uint8)

    def produce():
        for off in range(0, total, batch):
            yield off, data[:, off : off + batch]

    def consume(off, rec):
        out[:, off : off + rec.shape[1]] = rec

    run_staged_apply(
        be, coeffs, produce, consume,
        priority=priority, device_queue=queue, describe="test stream",
    )
    return out


# --------------------------------------------------- queue bit-identity


@pytest.mark.parametrize("kind", BACKENDS)
def test_queue_staged_apply_bit_identical(kind):
    """The scheduler path must be byte-for-byte the synchronous apply on
    every backend family, ragged tail included (acceptance criterion:
    XLA, interpret-mode Pallas, mesh, CPU, fallback)."""
    be = make_backend(kind)
    cpu = CpuBackend(CTX)
    q = DeviceQueue()
    coeffs = decode_coeffs((0, 13), tuple(range(1, 11)))
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (K, 3 * 4096 + 1217), dtype=np.uint8)
    got = staged_through_queue(be, q, coeffs, data, "foreground")
    assert np.array_equal(got, cpu.apply(coeffs, data)), kind
    assert q.inflight == 0


def test_concurrent_streams_interleave_bit_exact():
    """Three classes on ONE queue and ONE backend, concurrently: every
    stream's output is bit-exact and delivered in its own order (the
    interleaving correctness the tentpole must hold)."""
    be = CpuBackend(CTX)
    q = DeviceQueue(window=2)
    rng = np.random.default_rng(12)
    jobs = {
        "foreground": decode_coeffs((0,), tuple(range(1, 11))),
        "recovery": decode_coeffs((13,), tuple(range(10))),
        "scrub": decode_coeffs((2, 12), tuple(i for i in range(14) if i not in (2, 12))[:K]),
    }
    datas = {
        cls: rng.integers(0, 256, (K, 64 * 1024 + 321), dtype=np.uint8)
        for cls in jobs
    }
    results: dict = {}
    errors: list = []

    def run(cls):
        try:
            results[cls] = staged_through_queue(
                be, q, jobs[cls], datas[cls], cls, batch=4096
            )
        except BaseException as e:  # pragma: no cover
            errors.append((cls, e))

    threads = [threading.Thread(target=run, args=(c,)) for c in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    for cls, coeffs in jobs.items():
        assert np.array_equal(results[cls], be.apply(coeffs, datas[cls])), cls
    st = q.stats()
    assert all(st[c]["admitted"] == st[c]["drained"] > 0 for c in jobs)
    assert q.inflight == 0


# -------------------------------------------------------- policy / fairness


def _drive(q, cls, n, order, hold=None):
    s = q.stream(cls)
    try:
        for i in range(n):
            t, _ = s.dispatch(lambda: None, 10_000)
            order.append(cls)
            if hold is not None:
                hold()
            s.release(t)
    finally:
        s.close()


@pytest.mark.chaos
def test_saturating_recovery_cannot_starve_foreground():
    """window=1 + a recovery stream that always has work queued: an
    arriving foreground batch is admitted within a bounded number of
    admissions (batch-granularity preemption — the recovery stream
    yields the H2D slot), and foreground p99 wait stays bounded by a
    couple of batch times, not the rebuild's remaining length."""
    q = DeviceQueue(window=1, shares={"recovery": 0.10})
    order: list = []
    stop = threading.Event()

    def recovery_forever():
        s = q.stream("recovery")
        try:
            while not stop.is_set():
                t, _ = s.dispatch(lambda: None, 10_000)
                order.append("recovery")
                stop.wait(0.001)  # drain latency holding the slot
                s.release(t)
        finally:
            s.close()

    rt = threading.Thread(target=recovery_forever)
    rt.start()
    try:
        # let the rebuild saturate the chip first
        while len(order) < 5:
            stop.wait(0.001)
        _drive(q, "foreground", 30, order, hold=lambda: stop.wait(0.001))
    finally:
        stop.set()
        rt.join(timeout=30)
    idx = [i for i, c in enumerate(order) if c == "foreground"]
    gaps = [b - a for a, b in zip(idx, idx[1:])]
    # between consecutive foreground admissions at most 1-2 recovery
    # batches squeeze in (the 10% minimum share) — never a long run
    assert max(gaps) <= 3, gaps
    st = q.stats()
    # bounded foreground wait: admission never waited for more than a
    # few held batches (each held ~1ms; a starved stream would show a
    # wait comparable to the whole recovery run)
    assert st["foreground"]["wait_s_max"] < 1.0, st["foreground"]
    # no starvation the other way: recovery kept making progress while
    # foreground was active (non-zero share)
    assert any(c == "recovery" for c in order[idx[0] : idx[-1]])
    assert q.inflight == 0


def _contended_run(q, fg_cls, bg_cls, fg_batches=30):
    """Saturate `bg_cls`, then drive `fg_batches` of `fg_cls` through
    the contended queue; returns the admission order inside the
    foreground span."""
    order: list = []
    stop = threading.Event()

    def background():
        s = q.stream(bg_cls)
        try:
            while not stop.is_set():
                t, _ = s.dispatch(lambda: None, 10_000)
                order.append(bg_cls)
                stop.wait(0.001)
                s.release(t)
        finally:
            s.close()

    bt = threading.Thread(target=background)
    bt.start()
    try:
        while len(order) < 5:  # background saturates first
            stop.wait(0.001)
        _drive(q, fg_cls, fg_batches, order, hold=lambda: stop.wait(0.001))
    finally:
        stop.set()
        bt.join(timeout=30)
    span = [i for i, c in enumerate(order) if c == fg_cls]
    return order[span[0] : span[-1] + 1]


def test_background_minimum_share_and_work_conservation():
    """With foreground saturating, recovery still gets roughly its
    configured share of admissions (non-zero, clear minority); with no
    foreground at all, recovery runs at full speed (work-conserving,
    no pacing)."""
    q = DeviceQueue(window=1, shares={"recovery": 0.2})
    span = _contended_run(q, "foreground", "recovery")
    rec_during = sum(1 for c in span if c == "recovery")
    # share 0.2 -> roughly 1 recovery per 4 foreground inside the
    # contended span; wide slack, but BOTH non-zero and a minority
    assert rec_during > 0
    assert rec_during <= len(span) * 0.5
    # work conservation: alone, recovery admits immediately
    order2: list = []
    _drive(q, "recovery", 10, order2)
    assert order2 == ["recovery"] * 10
    assert q.stats()["recovery"]["wait_s_max"] < 1.0


def test_scrub_yields_to_recovery_but_not_starved():
    q = DeviceQueue(window=1, shares={"recovery": 0.2, "scrub": 0.1})
    span = _contended_run(q, "recovery", "scrub")
    scrub_during = sum(1 for c in span if c == "scrub")
    assert scrub_during > 0  # minimum share held against recovery
    assert scrub_during < len(span) * 0.5


def test_configure_knobs_and_registry():
    """configure() flips the process-wide enable + shares; for_backend
    returns one queue per backend instance; stats_snapshot surfaces
    per-class counters (the /status payload)."""
    be = CpuBackend(CTX)
    try:
        cfg = configure(enabled=True, window=6, shares={"recovery": 0.3})
        assert cfg["window"] == 6 and cfg["shares"]["recovery"] == 0.3
        q = for_backend(be)
        assert q is not None and for_backend(be) is q
        assert q.window == 6 and q.shares["recovery"] == 0.3
        # a shares dict REPLACES the whole map: omitted classes return
        # to defaults (one caller's override never sticks to the next)
        cfg = configure(shares={})
        assert cfg["shares"] == DEFAULT_SHARES
        assert q.shares == DEFAULT_SHARES
        assert for_backend(None) is None
        configure(enabled=False)
        assert for_backend(be) is None
        configure(enabled=True)
        q2 = for_backend(be)
        assert q2 is not None
        snap = stats_snapshot()
        assert any(s["backend"] == "CpuBackend" for s in snap)
        with pytest.raises(ECError):
            q2.stream("urgent")
        with pytest.raises(ECError):
            configure(shares={"bogus": 0.5})
    finally:
        # restore process-wide defaults for the rest of the suite
        configure(enabled=True, window=4, shares=dict(DEFAULT_SHARES))


# ------------------------------------------------- fault isolation (chaos)


@pytest.mark.chaos
def test_mid_stream_device_death_replays_only_victim_batches():
    """Two streams on one FallbackBackend queue; two injected to_host
    faults: exactly the faulted batches replay on CPU (bit-identical),
    the breaker stays closed (below threshold), later batches keep the
    device, and no window slot leaks."""
    fb = FallbackBackend(
        JaxBackend(CTX, impl="xla", n_devices=1),
        CpuBackend(CTX),
        breaker=CircuitBreaker(failure_threshold=50, reset_timeout=9999.0),
    )
    cpu = CpuBackend(CTX)
    q = DeviceQueue(window=2)
    rng = np.random.default_rng(21)
    c_fg = decode_coeffs((0,), tuple(range(1, 11)))
    c_rec = decode_coeffs((13,), tuple(range(10)))
    d_fg = rng.integers(0, 256, (K, 16 * 4096), dtype=np.uint8)
    d_rec = rng.integers(0, 256, (K, 16 * 4096), dtype=np.uint8)
    results: dict = {}
    errors: list = []

    def run(cls, coeffs, data):
        try:
            results[cls] = staged_through_queue(fb, q, coeffs, data, cls)
        except BaseException as e:  # pragma: no cover
            errors.append((cls, e))

    with faults.injected(
        "ec.backend.device.to_host",
        faults.io_error("device lost mid-drain"),
        when=faults.every(3),
        count=2,
    ):
        ts = [
            threading.Thread(target=run, args=("foreground", c_fg, d_fg)),
            threading.Thread(target=run, args=("recovery", c_rec, d_rec)),
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    assert not errors, errors
    # every byte of BOTH streams is bit-identical regardless of which
    # stream's batches the death hit (per-stream carried host copies)
    assert np.array_equal(results["foreground"], cpu.apply(c_fg, d_fg))
    assert np.array_equal(results["recovery"], cpu.apply(c_rec, d_rec))
    # only the in-flight faulted batches fell back; the device kept
    # serving everyone else (breaker never opened)
    assert fb.fallback_batches == 2
    assert fb.breaker.state == "closed"
    assert q.inflight == 0


@pytest.mark.chaos
def test_admission_timeout_fails_loudly_on_wedged_chip():
    """Slots held forever (a stream wedged in to_host against a hung
    device): another stream's admission must not freeze silently — past
    the admit deadline it raises ECError, the timed-out waiter leaves
    the queue, and the queue serves normally once the slot frees."""
    q = DeviceQueue(window=1, admit_timeout=0.2)
    hog = q.stream("recovery")
    ticket, _ = hog.dispatch(lambda: None, 1000)  # holds the only slot
    fg = q.stream("foreground")
    try:
        with pytest.raises(ECError, match="admission timed out"):
            fg.dispatch(lambda: None, 1000)
        assert q.stats()["foreground"]["depth"] == 0  # waiter removed
        hog.release(ticket)  # chip recovers -> service resumes
        t2, _ = fg.dispatch(lambda: None, 1000)
        fg.release(t2)
    finally:
        fg.close()
        hog.close()
    assert q.inflight == 0


@pytest.mark.chaos
def test_dying_stream_releases_slots_for_survivors():
    """A stream whose backend dies mid-pipeline (raw device error, no
    fallback) aborts alone: its window slots are released and another
    stream completes normally on the same queue afterwards."""

    class DyingBackend(CpuBackend):
        def __init__(self, ctx, die_after):
            super().__init__(ctx)
            self.calls = 0
            self.die_after = die_after

        def to_host(self, result):
            self.calls += 1
            if self.calls > self.die_after:
                raise OSError("device vanished")
            return super().to_host(result)

    q = DeviceQueue(window=2)
    dying = DyingBackend(CTX, die_after=2)
    healthy = CpuBackend(CTX)
    coeffs = decode_coeffs((1,), tuple(i for i in range(14) if i != 1)[:K])
    rng = np.random.default_rng(31)
    data = rng.integers(0, 256, (K, 12 * 4096), dtype=np.uint8)
    with pytest.raises(OSError):
        staged_through_queue(dying, q, coeffs, data, "recovery")
    assert q.inflight == 0, "dying stream leaked window slots"
    got = staged_through_queue(healthy, q, coeffs, data, "foreground")
    assert np.array_equal(got, healthy.apply(coeffs, data))
    assert q.inflight == 0


@pytest.mark.chaos
def test_queue_breaker_gating_preserved():
    """Every dispatch failing opens the breaker THROUGH the queue path;
    output stays bit-identical (CPU serves) — the PR 3 fail-closed
    semantics survive the scheduler."""
    fb = FallbackBackend(
        JaxBackend(CTX, impl="xla", n_devices=1),
        CpuBackend(CTX),
        breaker=CircuitBreaker(failure_threshold=3, reset_timeout=9999.0),
    )
    cpu = CpuBackend(CTX)
    q = DeviceQueue()
    coeffs = decode_coeffs((5,), tuple(i for i in range(14) if i != 5)[:K])
    data = np.random.default_rng(41).integers(
        0, 256, (K, 8 * 4096), dtype=np.uint8
    )
    with faults.injected(
        "ec.backend.device.apply_staged", faults.io_error("device dead")
    ):
        got = staged_through_queue(fb, q, coeffs, data, "recovery")
    assert np.array_equal(got, cpu.apply(coeffs, data))
    assert fb.breaker.state == "open"
    assert fb.fallback_batches >= 3


# ------------------------------------------- store-level shared cache


def make_ec_volume_files(tmp_path, vid, needles=16, seed=0):
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), vid)
    payloads = {}
    for i in range(1, needles + 1):
        size = int(rng.integers(1, 40_000))
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x1000 + i, needle_id=i, data=data))
        payloads[i] = data
    v.close()
    base = Volume.base_file_name(str(tmp_path), "", vid)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    # degrade: lose shard 0 so reads reconstruct (and populate the cache)
    os.unlink(base + CTX.to_ext(0))
    os.unlink(base + ".dat")  # EC-only volume (store mounts the .ecx)
    os.unlink(base + ".idx")
    return base, payloads


def test_store_level_shared_interval_cache(tmp_path):
    """One byte budget across all EcVolumes: both volumes populate the
    SAME ChunkCache under volume-namespaced keys; invalidating one
    volume's shard keeps the other volume's extents; unmounting a
    volume frees only its own entries."""
    _, p1 = make_ec_volume_files(tmp_path, 1, seed=1)
    _, p2 = make_ec_volume_files(tmp_path, 2, seed=2)
    store = Store([str(tmp_path)], ec_backend="cpu")
    try:
        ev1 = store.find_ec_volume(1)
        ev2 = store.find_ec_volume(2)
        assert ev1 is not None and ev2 is not None
        assert ev1.interval_cache is store.ec_interval_cache
        assert ev2.interval_cache is ev1.interval_cache
        for i, data in p1.items():
            assert ev1.read_needle(i, cookie=0x1000 + i).data == data
        for i, data in p2.items():
            assert ev2.read_needle(i, cookie=0x1000 + i).data == data
        cache = store.ec_interval_cache
        keys = list(cache._data)
        assert any(k.startswith("1:") for k in keys)
        assert any(k.startswith("2:") for k in keys)
        assert cache.size_bytes <= cache.capacity
        # invalidate vol 1 shard 0: vol 2's extents survive
        v2_bytes = sum(
            len(v) for k, v in cache._data.items() if k.startswith("2:")
        )
        ev1.reopen_shards([0])
        assert not any(k.startswith("1:0:") for k in cache._data)
        assert sum(
            len(v) for k, v in cache._data.items() if k.startswith("2:")
        ) == v2_bytes
        # unmount vol 2: its namespace drains, budget freed, vol 1 reads
        # still serve (and re-populate under the shared budget)
        store.unmount_ec_volume(2)
        assert not any(k.startswith("2:") for k in cache._data)
        nid = next(iter(p1))
        assert ev1.read_needle(nid, cookie=0x1000 + nid).data == p1[nid]
    finally:
        store.close()


def test_store_cache_budget_zero_disables(tmp_path):
    make_ec_volume_files(tmp_path, 1, seed=3)
    store = Store([str(tmp_path)], ec_backend="cpu", ec_interval_cache_bytes=0)
    try:
        assert store.ec_interval_cache is None
        ev = store.find_ec_volume(1)
        assert ev is not None and ev.interval_cache is None
    finally:
        store.close()


def test_standalone_ec_volume_keeps_private_cache(tmp_path):
    """EcVolume constructed without a Store keeps its own budget (the
    embedded / test shape) — namespacing is harmless there."""
    from seaweedfs_tpu.ec import EcVolume

    _, payloads = make_ec_volume_files(tmp_path, 1, seed=4)
    ev = EcVolume(str(tmp_path), 1, backend_name="cpu")
    try:
        assert ev._shared_cache is False
        nid = next(iter(payloads))
        assert ev.read_needle(nid, cookie=0x1000 + nid).data == payloads[nid]
        assert ev.interval_cache.size_bytes > 0
        assert all(k.startswith("1:") for k in ev.interval_cache._data)
    finally:
        ev.close()


# --------------------------------------------------------------------------
# PR 33: what the queue says when two classes meet. Every queue below
# runs on a manual clock: a waiter's seconds are the seconds the test
# moved it by, whatever the machine did meanwhile.
# --------------------------------------------------------------------------


class ManualClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def _until(cond, what: str) -> None:
    """Hand the interpreter to the other threads until `cond()`: order,
    not time (the bound only ends a test that would hang)."""
    tick = threading.Event()
    for _ in range(20_000):
        if cond():
            return
        tick.wait(0.0005)
    raise AssertionError(f"never saw {what}")


def _waiter(q, cls, order, span=None):
    """A thread that asks for one slot of `cls` and holds it until told."""
    go = threading.Event()

    def run():
        with q.admission(cls, 1000, span=span):
            order.append(cls)
            go.wait(30)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, go


def _full_of_recovery(clock, window=4):
    q = DeviceQueue(window=window, clock=clock, label="chip0")
    hog = q.stream("recovery")
    tickets = [hog.dispatch(lambda: None, 10_000)[0] for _ in range(window)]
    return q, hog, tickets


def test_foreground_that_finds_the_window_full_of_recovery_is_counted_and_goes_first():
    clock = ManualClock()
    q, hog, tickets = _full_of_recovery(clock)
    order: list = []
    rec_t, rec_go = _waiter(q, "recovery", order)
    _until(lambda: q.stats()["recovery"]["depth"] == 1, "the recovery waiter queued")
    fg_t, fg_go = _waiter(q, "foreground", order)
    _until(lambda: q.stats()["foreground"]["depth"] == 1, "the foreground waiter queued")
    clock.now += 2.5
    hog.release(tickets[0])  # one slot frees: the later, higher class takes it
    _until(lambda: order == ["foreground"], "the foreground waiter admitted")
    st = q.stats()
    assert st["foreground"]["blocked"] == {"recovery": 1}
    assert st["foreground"]["blocked_s"] == {"recovery": 2.5}
    assert st["foreground"]["wait_s_total"] == 2.5
    assert st["recovery"]["blocked"] == {} and st["recovery"]["depth"] == 1
    clock.now += 1.0
    fg_go.set()
    fg_t.join(10)
    _until(lambda: order == ["foreground", "recovery"], "the recovery waiter admitted")
    st = q.stats()
    assert st["recovery"]["blocked"] == {"recovery": 1}  # four of its own held it out
    assert st["recovery"]["blocked_s"] == {"recovery": 3.5}
    rec_go.set()
    rec_t.join(10)
    hog.close()
    assert q.inflight == 0


def test_a_waiter_that_finds_a_free_slot_counts_nothing():
    clock = ManualClock()
    q = DeviceQueue(window=4, clock=clock, label="chip0")
    hog = q.stream("recovery")
    held = [hog.dispatch(lambda: None, 10_000)[0] for _ in range(3)]
    clock.now += 5.0
    with q.admission("foreground", 1000) as ticket:
        assert ticket.blocked is None and ticket.wait_s == 0.0
    st = q.stats()
    assert st["foreground"]["blocked"] == {} and st["foreground"]["blocked_s"] == {}
    assert st["recovery"]["blocked"] == {}
    for t in held:
        hog.release(t)


@pytest.mark.parametrize("held, by", [
    (("recovery",) * 4, "recovery"),
    (("recovery", "recovery", "foreground", "foreground"), "recovery"),  # the tie
    (("recovery", "foreground", "foreground", "foreground"), "foreground"),
    (("scrub", "scrub", "scrub", "recovery"), "scrub"),
])
def test_a_full_window_is_laid_to_the_class_that_holds_most_of_it(held, by):
    clock = ManualClock()
    q = DeviceQueue(window=4, clock=clock, label="chip0")
    streams = {c: q.stream(c) for c in set(held)}
    tickets = [(c, streams[c].dispatch(lambda: None, 10_000)[0]) for c in held]
    order: list = []
    t, go = _waiter(q, "foreground", order)
    _until(lambda: q.stats()["foreground"]["depth"] == 1, "the waiter queued")
    clock.now += 0.25
    cls, first = tickets[0]
    streams[cls].release(first)
    _until(lambda: order == ["foreground"], "the waiter admitted")
    assert q.stats()["foreground"]["blocked"] == {by: 1}
    assert q.stats()["foreground"]["blocked_s"] == {by: 0.25}
    go.set()
    t.join(10)
    for c, ticket in tickets[1:]:
        streams[c].release(ticket)
    assert q.inflight == 0


def test_slot_seconds_by_class_add_up_to_the_time_the_slots_were_held():
    clock = ManualClock()
    q = DeviceQueue(window=4, clock=clock, label="chip0")
    rec, fg = q.stream("recovery"), q.stream("foreground")
    r1, _ = rec.dispatch(lambda: None, 10_000)
    clock.now += 1.0
    r2, _ = rec.dispatch(lambda: None, 10_000)
    f1, _ = fg.dispatch(lambda: None, 1000)
    clock.now += 0.5
    fg.release(f1)  # held 0.5
    assert q.stats()["foreground"]["slot_s"] == 0.5
    assert q.stats()["recovery"]["slot_s"] == 0.0  # counted at release, whole
    clock.now += 2.0
    rec.release(r1)  # held 3.5
    rec.release(r2)  # held 2.5
    rec.release(r2)  # a second release counts nothing
    st = q.stats()
    assert st["recovery"]["slot_s"] == 6.0 and st["foreground"]["slot_s"] == 0.5
    assert st["scrub"]["slot_s"] == 0.0


def test_a_chip_full_on_the_ledger_blocks_like_a_full_window():
    """Two scopes' queues on one physical chip: the second's window is
    empty, the chip's budget is not, and the waiter is laid to the class
    that holds the ledger's slots."""
    from seaweedfs_tpu.ec.device_queue import ResidencyLedger

    clock = ManualClock()
    ledger = ResidencyLedger(budget=2, clock=clock)
    a = DeviceQueue(window=4, clock=clock, label="chip0", residency=ledger, tenant="a")
    b = DeviceQueue(window=4, clock=clock, label="chip0", residency=ledger, tenant="b")
    hog = a.stream("recovery")
    held = [hog.dispatch(lambda: None, 10_000)[0] for _ in range(2)]
    order: list = []
    t, go = _waiter(b, "foreground", order)
    _until(lambda: len(ledger._waiters) == 1, "the waiter at the ledger")
    clock.now += 0.75
    hog.release(held[0])
    _until(lambda: order == ["foreground"], "the waiter admitted")
    st = b.stats()["foreground"]
    assert st["blocked"] == {"recovery": 1} and st["blocked_s"] == {"recovery": 0.75}
    assert a.stats()["recovery"]["blocked"] == {}
    go.set()
    t.join(10)
    hog.release(held[1])
    assert ledger.snapshot()["chips"]["chip0"]["inflight"] == 0


def test_the_new_counters_are_on_metrics_and_in_the_snapshot():
    from seaweedfs_tpu.ec.device_queue import QueueScope
    from seaweedfs_tpu.utils import metrics

    scope = QueueScope(window=1, residency=False)
    be = CpuBackend(CTX)
    q = scope.for_backend(be)
    hog = q.stream("recovery")
    ticket, _ = hog.dispatch(lambda: None, 10_000)
    order: list = []
    t, go = _waiter(q, "foreground", order)
    _until(lambda: q.stats()["foreground"]["depth"] == 1, "the waiter queued")
    hog.release(ticket)
    _until(lambda: order == ["foreground"], "the waiter admitted")
    go.set()
    t.join(10)
    (snap,) = scope.stats_snapshot()
    assert snap["classes"]["foreground"]["blocked"] == {"recovery": 1}
    assert set(snap["classes"]["recovery"]) >= {"blocked", "blocked_s", "slot_s"}
    text = metrics.REGISTRY.render().decode()
    for name in ("sw_ec_queue_blocked_total", "sw_ec_queue_blocked_seconds_total",
                 "sw_ec_queue_slot_seconds_total"):
        assert name in text, name
    assert f'sw_ec_queue_blocked_total{{cls="foreground",by="recovery",chip="{q.label}"}}' in text


def test_the_window_full_event_lands_on_the_armed_span_and_the_disarmed_path_reads_no_clock(
    monkeypatch,
):
    from seaweedfs_tpu.utils import trace

    clock = ManualClock()
    q, hog, tickets = _full_of_recovery(clock, window=2)
    trace.configure(enabled=True)
    try:
        trace.reset()
        sp = trace.start("ec.degraded_read")
        order: list = []
        t, go = _waiter(q, "foreground", order, span=sp)
        _until(lambda: q.stats()["foreground"]["depth"] == 1, "the waiter queued")
        clock.now += 0.5
        hog.release(tickets[0])
        _until(lambda: order == ["foreground"], "the waiter admitted")
        go.set()
        t.join(10)
        with q.admission("foreground", 1000, span=sp):  # a free slot: no event
            pass
        trace.finish(sp)
        doc = trace.traces()[-1]
        (ev,) = [e for e in doc["events"] if e["name"] == "window_full"]
        assert ev["attrs"] == {"by": "recovery", "held": {"recovery": 2}}
        assert doc["stages"]["admission_wait"]["seconds"] == 0.5
    finally:
        trace.configure(enabled=False)
        trace.reset()

    # disarmed: a blocked admission counts, and touches nothing of the tracer
    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"the disarmed path read the tracer's time.{name}")

    monkeypatch.setattr(trace, "time", NoClock())
    monkeypatch.setattr(trace, "_StageTimer", None)  # constructing one fails
    monkeypatch.setattr(trace.Span, "event", None)
    tickets[0], _ = hog.dispatch(lambda: None, 10_000)  # the window full again
    order = []
    t, go = _waiter(q, "foreground", order)
    _until(lambda: q.stats()["foreground"]["depth"] == 1, "the waiter queued")
    hog.release(tickets[1])
    _until(lambda: order == ["foreground"], "the waiter admitted")
    go.set()
    t.join(10)
    assert q.stats()["foreground"]["blocked"] == {"recovery": 2}
    hog.close()


def test_blocked_counts_and_slot_seconds_lose_no_update_under_contention():
    """More threads than cores on a window of 2, the interpreter
    switching every 10 us: what the queue counted is what the tickets it
    handed out say, to the unit."""
    import collections
    import sys

    q = DeviceQueue(window=2, label="chip0")
    tallies = [collections.Counter() for _ in range(24)]

    def work(n: int) -> None:
        cls = ("foreground", "recovery", "scrub")[n % 3]
        s = q.stream(cls)
        for _ in range(150):
            t, _ = s.dispatch(lambda: None, 1000)
            if t.blocked is not None:
                tallies[n][(cls, t.blocked[0])] += 1
                assert sum(t.blocked[1].values()) == 2  # a full window, at arrival
            s.release(t)
        s.close()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(n,), daemon=True) for n in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    want = sum(tallies, collections.Counter())
    st = q.stats()
    got = {(cls, by): n for cls in st for by, n in st[cls]["blocked"].items()}
    assert got == dict(want) and sum(want.values()) > 0
    assert all(st[c]["admitted"] == st[c]["drained"] == 8 * 150 for c in st)
    for c in st.values():
        assert sum(c["blocked_s"].values()) <= c["wait_s_total"] + 1e-6
        assert c["slot_s"] >= 0 and c["inflight"] == 0
    assert q.inflight == 0
