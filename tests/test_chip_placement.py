"""PR 5 pod-level stream placement tests: ChipPool routing EC streams
to single chips instead of column-slicing every stream across the mesh
(ec/chip_pool.py), the rows x bytes admission cost model, and the
per-Store scheduler scope.

Load-bearing properties:

- bit-identity: a stream placed on one chip produces byte-for-byte the
  mesh-sliced and CPU outputs (the placement decision is scheduling
  only);
- routing: deterministic least-loaded placement under skewed stream
  costs; a lone wide stream keeps the mesh in "auto", competing
  streams get chips; "mesh"/"chip" pin the policy;
- fault isolation: one chip dying replays only ITS streams' batches on
  CPU — sibling streams keep their chips and their own breakers;
- cost model: a 1-row reconstruction stream is admitted ~m x more often
  per unit of banked share credit than a parity-encode stream of equal
  width (heterogeneous-batch fairness);
- per-Store scopes: two Stores' scheduler configs no longer clobber
  each other (configure() stops being process-wide last-caller-wins).

The conftest forces an 8-device virtual CPU platform, so the mesh
backend (and therefore the pool) is real in every run.
"""

import os
import threading

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import (
    ChipPool,
    CpuBackend,
    ECContext,
    FallbackBackend,
    JaxBackend,
    QueueScope,
    ec_encode_volume,
    place_stream,
    pool_for,
)
from seaweedfs_tpu.ec.backend import _decode_coeffs
from seaweedfs_tpu.ec.bitrot import BitrotProtection
from seaweedfs_tpu.ec.device_queue import DeviceQueue, batch_cost
from seaweedfs_tpu.ec.pipeline import run_staged_apply
from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.storage.needle import Needle
from seaweedfs_tpu.storage.store import Store
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils.retry import CircuitBreaker

CTX = ECContext(10, 4)
K = CTX.data_shards
M = CTX.parity_shards


def decode_coeffs(targets, src):
    rs = gf256.ReedSolomon(K, CTX.parity_shards)
    return _decode_coeffs(rs.matrix, K, tuple(targets), tuple(src))


def run_stream(backend, queue, coeffs, data, priority="foreground", batch=4096):
    """One staged stream through an explicit (backend, queue) pair."""
    out = np.zeros((coeffs.shape[0], data.shape[1]), dtype=np.uint8)

    def produce():
        for off in range(0, data.shape[1], batch):
            yield off, data[:, off : off + batch]

    def consume(off, rec):
        out[:, off : off + rec.shape[1]] = rec

    run_staged_apply(
        backend, coeffs, produce, consume,
        priority=priority, device_queue=queue, describe="placement test",
    )
    return out


# ------------------------------------------------------------------- pool


def test_pool_exists_only_for_mesh_backends():
    mesh_be = JaxBackend(CTX)  # 8 virtual devices -> column mesh
    pool = pool_for(mesh_be)
    assert pool is not None and pool.n_chips == 8
    assert pool_for(mesh_be) is pool  # one pool per backend instance
    assert pool_for(CpuBackend(CTX)) is None
    assert pool_for(JaxBackend(CTX, impl="xla", n_devices=1)) is None
    assert pool_for(None) is None
    # chip labels are device ids — these key the queue stats/metrics
    assert pool.labels[0].startswith("cpu:")
    assert len(set(pool.labels)) == 8
    # two backends over the SAME physical chips (another shard ratio)
    # get their own pool (ctx-specific chip backends) but share the
    # LOAD ledger: a stream placed by one is visible to the other
    be2 = JaxBackend(ECContext(5, 2))
    pool2 = pool_for(be2)
    assert pool2 is not pool
    i, _, release = pool.acquire(77)
    try:
        assert not pool2.idle()
        assert pool2.loads()[i] == 77
    finally:
        release()
    assert pool2.idle() and pool.idle()


def test_least_loaded_routing_under_skewed_costs():
    """Deterministic routing core (no jax): streams with skewed cost
    hints spread by least outstanding cost, ties to the lowest index;
    releases drain the load so the pool returns to idle."""
    made = []
    pool = ChipPool(
        devices=list(range(4)),
        make_chip=lambda d: made.append(d) or f"chip{d}",
        labels=[f"c{d}" for d in range(4)],
    )
    assert pool.idle()
    i1, be1, rel1 = pool.acquire(100)  # heavy stream -> chip 0
    assert (i1, be1) == (0, "chip0")
    picks = [pool.acquire(1) for _ in range(3)]  # light -> 1, 2, 3
    assert [p[0] for p in picks] == [1, 2, 3]
    # next light stream lands on the least-loaded (chip 1, load 1) —
    # NOT round-robin back to the heavy chip 0 (load 100)
    i5, _, rel5 = pool.acquire(1)
    assert i5 == 1
    assert pool.loads() == [100, 2, 1, 1]
    assert not pool.idle()
    rel1()
    rel1()  # idempotent
    for _, _, rel in picks:
        rel()
    rel5()
    assert pool.loads() == [0, 0, 0, 0]
    assert pool.idle()
    # chips were constructed lazily, once each, only for used indices
    assert made == [0, 1, 2, 3]


@pytest.mark.parametrize("seed", [0xA11, 0xA12, 0xA13])
def test_routing_replays_the_documented_policy_for_seeded_arrivals(seed):
    """Least outstanding cost, ties to the lowest index, replayed by
    hand for a seeded arrival order with releases in between: the pool
    must match it placement for placement, and drain to idle."""
    rng = np.random.default_rng(seed)
    pool = ChipPool(range(8), lambda d: f"chip{d}")
    loads = [0] * 8
    held = []
    for cost in (int(c) for c in rng.integers(1, 1000, 48)):
        want = min(range(8), key=lambda j: (loads[j], j))
        got, be, release = pool.acquire(cost)
        assert (got, be) == (want, f"chip{want}")
        loads[want] += cost
        held.append((want, cost, release))
        if rng.random() < 0.3:  # a stream ends: its cost leaves its chip
            j, c, rel = held.pop(int(rng.integers(len(held))))
            rel()
            loads[j] -= c
        assert pool.loads() == loads
    for _, _, rel in held:
        rel()
    assert pool.idle() and pool.loads() == [0] * 8


def test_wide_lone_stream_keeps_mesh_competing_streams_get_chips():
    be = JaxBackend(CTX)
    pool = pool_for(be)
    scope = QueueScope(placement="auto")
    # lone wide stream on an idle pod: mesh slicing wins — and it
    # charges EVERY chip, so the pod reads busy while it runs
    p_wide = place_stream(be, "foreground", scope=scope, wide=True,
                          cost_hint=1000)
    assert p_wide.chip is None and p_wide.backend is be
    assert not pool.idle() and all(l > 0 for l in pool.loads())
    # a second wide stream mid-encode must NOT stack onto the mesh
    # queue behind the first — the pod is busy, it gets a chip
    p_wide2 = place_stream(be, "foreground", scope=scope, wide=True)
    assert p_wide2.chip is not None
    p_wide2.close()
    p_wide.close()
    assert pool.idle()
    # a competing stream exists: the wide stream gets a chip too
    p1 = place_stream(be, "foreground", scope=scope, cost_hint=10)
    assert p1.chip is not None
    p2 = place_stream(be, "foreground", scope=scope, cost_hint=10, wide=True)
    assert p2.chip is not None and p2.chip != p1.chip
    p1.close()
    p2.close()
    # pinned modes — a pinned-mesh stream keeps the mesh but still
    # charges the pod (another scope's wide-auto arrival must not see
    # an idle pod and stack a second column-sliced stream)
    p_mesh = place_stream(be, "foreground",
                          scope=QueueScope(placement="mesh"))
    assert p_mesh.chip is None and p_mesh.backend is be
    assert not pool.idle()
    p_auto_wide = place_stream(be, "foreground", scope=scope, wide=True)
    assert p_auto_wide.chip is not None
    p_auto_wide.close()
    p_mesh.close()
    p = place_stream(be, "foreground",
                     scope=QueueScope(placement="chip"), wide=True)
    assert p.chip is not None
    p.close()
    assert pool_for(be).idle()
    # non-wide small stream in auto mode routes to a chip
    p = place_stream(be, "recovery", scope=scope)
    assert p.chip is not None
    p.close()


def test_scheduler_disabled_disables_placement():
    be = JaxBackend(CTX)
    scope = QueueScope(enabled=False)
    p = place_stream(be, "foreground", scope=scope)
    assert p.queue is None and p.chip is None and p.backend is be
    p.close()


# ------------------------------------------------------- bit-identity


def test_pod_sharded_pjit_encode_bit_identical(monkeypatch):
    """ISSUE 15: the wide/mesh path's explicit NamedSharding/pjit
    encode (stripe-axis-constrained, full device mesh) is bit-identical
    to the shard_map lowering, the single-device chip path, and the
    CPU truth — ragged tail included — and the knob really selects the
    lowering."""
    from seaweedfs_tpu.ops.rs_jax import RSJax
    from seaweedfs_tpu.parallel import MeshRS, make_mesh, pad_cols

    rng = np.random.default_rng(0xB0D)
    data = rng.integers(0, 256, (K, 3 * 4096 + 131), dtype=np.uint8)
    want = CpuBackend(CTX).encode(data)

    rs = RSJax(K, M, impl="xla")
    mesh = make_mesh(8)

    def mesh_encode(m):
        padded, n = pad_cols(data, m.n_devices)
        return np.asarray(m.encode(m.put(padded)), dtype=np.uint8)[:, :n]

    monkeypatch.delenv("SEAWEED_EC_POD_PJIT", raising=False)
    pod = MeshRS(rs, mesh)
    assert pod.pod_sharded, "xla impl must take the pjit pod lowering"
    got_pjit = mesh_encode(pod)

    monkeypatch.setenv("SEAWEED_EC_POD_PJIT", "0")
    legacy = MeshRS(rs, mesh)
    assert not legacy.pod_sharded
    got_shard_map = mesh_encode(legacy)

    single = JaxBackend(CTX, impl="xla", n_devices=1)
    got_single = np.asarray(
        single.to_host(single.encode_staged(single.to_device(data))),
        dtype=np.uint8,
    )
    assert np.array_equal(got_pjit, want)
    assert np.array_equal(got_shard_map, want)
    assert np.array_equal(got_single, want)


def test_chip_vs_mesh_vs_single_bit_identical():
    """The same stream through a placed chip, the column mesh, and a
    single-device backend yields byte-identical output (ragged tail
    included) — the acceptance bit-identity criterion."""
    mesh_be = JaxBackend(CTX)
    single_be = JaxBackend(CTX, impl="xla", n_devices=1)
    cpu = CpuBackend(CTX)
    coeffs = decode_coeffs((0, 13), tuple(range(1, 11)))
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (K, 5 * 4096 + 997), dtype=np.uint8)
    want = cpu.apply(coeffs, data)

    chip_scope = QueueScope(placement="chip")
    placement = place_stream(mesh_be, "foreground", scope=chip_scope,
                             cost_hint=2 * data.shape[1])
    assert placement.chip is not None
    try:
        got_chip = run_stream(placement.backend, placement.queue, coeffs, data)
    finally:
        placement.close()
    got_mesh = run_stream(mesh_be, DeviceQueue(), coeffs, data)
    got_single = run_stream(single_be, DeviceQueue(), coeffs, data)
    assert np.array_equal(got_chip, want)
    assert np.array_equal(got_mesh, want)
    assert np.array_equal(got_single, want)


def test_encode_volume_placed_bit_identical_to_cpu(tmp_path):
    """Full ec_encode_volume through the mesh backend under chip
    placement: shard bytes and .ecsum CRCs equal the CPU encode —
    the encoder's placement integration is output-invisible."""
    rng = np.random.default_rng(6)
    vol = Volume(str(tmp_path), 1, needle_map_kind="memory")
    for nid in range(1, 6):
        vol.write_needle(Needle(
            cookie=9, needle_id=nid,
            data=rng.integers(0, 256, 50_000, dtype=np.uint8).tobytes(),
        ))
    vol.flush()
    base = vol.base_file_name(str(tmp_path), "", 1)
    vol.close()

    mesh_be = JaxBackend(CTX)
    pool = pool_for(mesh_be)
    ec_encode_volume(
        base, CTX, backend=mesh_be, batch_size=32 * 1024 + 7,
        scheduler=QueueScope(placement="chip"),
    )
    assert pool.idle()  # encode stream released its chip
    placed_prot = BitrotProtection.load(base + ".ecsum")
    shard_bytes = {}
    for i in range(CTX.total):
        with open(base + CTX.to_ext(i), "rb") as f:
            shard_bytes[i] = f.read()
        os.unlink(base + CTX.to_ext(i))
    os.unlink(base + ".ecsum")

    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    cpu_prot = BitrotProtection.load(base + ".ecsum")
    assert placed_prot.shard_crcs == cpu_prot.shard_crcs
    assert placed_prot.shard_sizes == cpu_prot.shard_sizes
    for i in range(CTX.total):
        with open(base + CTX.to_ext(i), "rb") as f:
            assert f.read() == shard_bytes[i], f"shard {i} differs"


# --------------------------------------------------------- cost model


def test_cost_model_one_row_reconstruction_not_starved():
    """window=1, recovery share 0.2: against a saturating foreground
    ENCODE-cost stream (m=4 rows/batch), a recovery stream of 1-row
    reconstruction batches is admitted ~m x more often than the old
    byte-denominated accounting allowed — its batches cost 1/m as much,
    so the same banked credit covers m x more of them."""
    W = 10_000
    q = DeviceQueue(window=1, shares={"recovery": 0.2})
    order: list = []
    stop = threading.Event()

    def recovery_one_row():
        s = q.stream("recovery")
        try:
            while not stop.is_set():
                t, _ = s.dispatch(lambda: None, batch_cost(1, W))
                order.append("recovery")
                stop.wait(0.001)
                s.release(t)
        finally:
            s.close()

    rt = threading.Thread(target=recovery_one_row)
    rt.start()
    try:
        while len(order) < 5:
            stop.wait(0.001)
        s = q.stream("foreground")
        try:
            for _ in range(40):
                t, _ = s.dispatch(lambda: None, batch_cost(4, W))
                order.append("foreground")
                stop.wait(0.001)
                s.release(t)
        finally:
            s.close()
    finally:
        stop.set()
        rt.join(timeout=30)
    span = [i for i, c in enumerate(order) if c == "foreground"]
    window = order[span[0] : span[-1] + 1]
    fg = sum(1 for c in window if c == "foreground")
    rec = sum(1 for c in window if c == "recovery")
    # credit per fg batch = 4W * 0.2/0.8 = W = one whole 1-row batch:
    # expect ~1 recovery admission per foreground admission. The old
    # byte accounting (every batch = k*W bytes) would yield ~0.25.
    assert rec >= fg * 0.5, (fg, rec)
    assert rec <= fg * 2.0, (fg, rec)
    assert q.inflight == 0


def test_queue_cost_accounting_sums_to_dispatched_work():
    q = DeviceQueue(window=2)
    s = q.stream("foreground")
    costs = [batch_cost(4, w) for w in (100, 7, 4096, 1)]
    try:
        for c in costs:
            t, _ = s.dispatch(lambda: None, c)
            s.release(t)
    finally:
        s.close()
    st = q.stats()["foreground"]
    assert st["admitted_cost"] == st["drained_cost"] == sum(costs)
    assert st["admitted"] == st["drained"] == len(costs)
    assert q.load() == 0 and q.inflight == 0


# --------------------------------------------------- chaos: chip death


@pytest.mark.chaos
def test_chip_death_isolates_its_streams():
    """Two streams placed on two chips of one pool; one chip's to_host
    dies repeatedly. Only the victim chip's batches replay on CPU
    (bit-identical), the sibling chip's stream never falls back, and
    each chip's OWN breaker sees the failures."""
    fb = FallbackBackend(
        JaxBackend(CTX, impl="xla", n_devices=8),
        CpuBackend(CTX),
        breaker=CircuitBreaker(failure_threshold=50, reset_timeout=9999.0),
    )
    assert fb.primary._mesh_rs is not None  # 8-dev mesh engaged
    scope = QueueScope(placement="chip")
    p0 = place_stream(fb, "foreground", scope=scope, cost_hint=100)
    p1 = place_stream(fb, "recovery", scope=scope, cost_hint=100)
    assert p0.chip != p1.chip
    victim_be, sibling_be = p0.backend, p1.backend
    assert victim_be.chip_label != sibling_be.chip_label
    # per-chip FallbackBackends with per-chip breakers
    assert victim_be is not fb and sibling_be is not fb
    assert victim_be.breaker is not sibling_be.breaker

    cpu = CpuBackend(CTX)
    c_fg = decode_coeffs((0,), tuple(range(1, 11)))
    c_rec = decode_coeffs((13,), tuple(range(10)))
    rng = np.random.default_rng(21)
    d_fg = rng.integers(0, 256, (K, 12 * 4096), dtype=np.uint8)
    d_rec = rng.integers(0, 256, (K, 12 * 4096), dtype=np.uint8)

    victim_label = victim_be.chip_label
    state = {"fired": 0}

    def kill_victim_chip(ctx):
        if ctx.get("chip") == victim_label and state["fired"] < 2:
            state["fired"] += 1
            raise faults.InjectedIOError(f"chip {victim_label} died")

    results: dict = {}
    errors: list = []

    def run(name, placement, coeffs, data, priority):
        try:
            results[name] = run_stream(
                placement.backend, placement.queue, coeffs, data, priority
            )
        except BaseException as e:  # pragma: no cover
            errors.append((name, e))
        finally:
            placement.close()

    with faults.injected(
        "ec.backend.device.to_host", kill_victim_chip, when=faults.always()
    ):
        ts = [
            threading.Thread(
                target=run, args=("victim", p0, c_fg, d_fg, "foreground")
            ),
            threading.Thread(
                target=run, args=("sibling", p1, c_rec, d_rec, "recovery")
            ),
        ]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=120)
    assert not errors, errors
    assert state["fired"] == 2
    assert np.array_equal(results["victim"], cpu.apply(c_fg, d_fg))
    assert np.array_equal(results["sibling"], cpu.apply(c_rec, d_rec))
    # isolation: only the victim chip fell back; its sibling kept its
    # chip and a clean breaker
    assert victim_be.fallback_batches == 2
    assert sibling_be.fallback_batches == 0
    assert sibling_be.breaker.state == "closed"
    assert victim_be.breaker.state == "closed"  # below threshold
    assert fb.fallback_batches == 0  # the pooled wrapper never dispatched
    assert pool_for(fb).idle()


# ------------------------------------------------- per-Store scopes


def make_degraded_ec_volume(tmp_path, vid, seed=0):
    rng = np.random.default_rng(seed)
    v = Volume(str(tmp_path), vid)
    payloads = {}
    for i in range(1, 9):
        data = rng.integers(0, 256, int(rng.integers(1, 30_000)),
                            dtype=np.uint8).tobytes()
        v.write_needle(Needle(cookie=0x1000 + i, needle_id=i, data=data))
        payloads[i] = data
    v.close()
    base = Volume.base_file_name(str(tmp_path), "", vid)
    ec_encode_volume(base, CTX, backend=CpuBackend(CTX))
    os.unlink(base + CTX.to_ext(0))  # degrade: reads reconstruct
    os.unlink(base + ".dat")
    os.unlink(base + ".idx")
    return payloads


def test_per_store_scheduler_scope(tmp_path):
    """A Store with scheduler knobs gets its OWN QueueScope (threaded
    to its EcVolumes like the interval cache); a bare Store rides the
    process-wide default; two configured Stores never clobber each
    other's config."""
    from seaweedfs_tpu.ec.device_queue import default_scope

    d1 = tmp_path / "s1"
    d2 = tmp_path / "s2"
    d1.mkdir()
    d2.mkdir()
    payloads = make_degraded_ec_volume(d1, 1, seed=7)
    make_degraded_ec_volume(d2, 1, seed=8)

    s1 = Store([str(d1)], ec_backend="cpu", ec_queue_window=2,
               ec_placement="mesh")
    s2 = Store([str(d2)], ec_backend="cpu",
               ec_queue_shares={"recovery": 0.5})
    s3 = Store([str(tmp_path)], ec_backend="cpu")
    try:
        assert s1.ec_scheduler is not s2.ec_scheduler
        assert s3.ec_scheduler is default_scope()
        cfg1 = s1.ec_scheduler.configure()
        cfg2 = s2.ec_scheduler.configure()
        assert cfg1["window"] == 2 and cfg1["placement"] == "mesh"
        assert cfg2["window"] != 2 and cfg2["shares"]["recovery"] == 0.5
        assert cfg2["placement"] == "auto"
        # one tenant reconfiguring stays inside its scope
        s1.ec_scheduler.configure(shares={"scrub": 0.3})
        assert s2.ec_scheduler.configure()["shares"]["scrub"] != 0.3
        # the scope reaches the mounted EC volumes (degraded-read path)
        ev = s1.find_ec_volume(1)
        assert ev is not None and ev.scheduler is s1.ec_scheduler
        nid = next(iter(payloads))
        assert ev.read_needle(nid, cookie=0x1000 + nid).data == payloads[nid]
        # per-scope stats snapshots are disjoint
        assert isinstance(s1.ec_scheduler.stats_snapshot(), list)
    finally:
        s1.close()
        s2.close()
        s3.close()


# ----------------------------------------------- live load-feedback routing


def test_live_queue_load_steers_routing_and_is_recorded():
    """PR 14 acceptance: routing reads LIVE DeviceQueue.load(), not just
    the static placed-cost ledger — skew one chip's queue and watch the
    next stream land elsewhere, with the decision's signal source and
    live loads recorded as a span event."""
    from seaweedfs_tpu.utils import trace

    be = JaxBackend(CTX)
    pool = pool_for(be)
    scope = QueueScope(placement="chip")
    # ledger idle: with no live signal the deterministic pick is chip 0
    p0 = place_stream(be, "foreground", scope=scope, cost_hint=1)
    assert p0.chip == 0
    p0.close()
    # skew chip 0's LIVE queue load (an admission the ledger never saw:
    # the one-shot gateway-read shape) and the next stream must follow
    # the live signal to chip 1 even though the ledger reads all-zero
    q0 = scope.for_backend(pool.chip_backend(0))
    trace.configure(enabled=True, ring_size=64, slow_op_s=0.0)
    try:
        with q0.admission("foreground", 50_000):
            assert q0.load() == 50_000
            sp = trace.start("ec.encode", name="live-routing-test")
            p1 = place_stream(be, "foreground", scope=scope,
                              cost_hint=1, span=sp)
            trace.finish(sp)
            assert p1.chip is not None and p1.chip != 0
            p1.close()
            ev = [e for e in sp.to_dict()["events"]
                  if e["name"] == "placement"]
            assert ev, "placement decision must be recorded"
            attrs = ev[-1]["attrs"]
            assert attrs["signal"] == "live"
            assert attrs["live_loads"][0] == 50_000
            assert attrs["chip"] == pool.labels[p1.chip]
        # queue drained: the live signal is gone, chip 0 wins again
        p2 = place_stream(be, "foreground", scope=scope, cost_hint=1)
        assert p2.chip == 0
        p2.close()
    finally:
        trace.configure(enabled=False, slow_op_s=0.0)
        trace.reset()


def test_open_breaker_repels_placement():
    """A chip whose fallback breaker is OPEN (its streams are running
    on CPU) loses routing to any healthy sibling, however the ledger
    and queue loads compare."""
    base = FallbackBackend(JaxBackend(CTX), CpuBackend(CTX))
    pool = pool_for(base)
    scope = QueueScope(placement="chip")
    chip0 = pool.chip_backend(0)
    assert isinstance(chip0, FallbackBackend)
    scope.for_backend(chip0)  # materialize the queue (its label carries
    # the breaker state into queue_loads)
    for _ in range(chip0.breaker.failure_threshold):
        chip0.breaker.record_failure()
    assert chip0.breaker.state == "open"
    try:
        p = place_stream(base, "foreground", scope=scope, cost_hint=1)
        assert p.chip is not None and p.chip != 0
        p.close()
    finally:
        chip0.breaker.record_success()


def test_placement_decision_counter_by_signal():
    from seaweedfs_tpu.ec.chip_pool import _placement_decisions

    be = JaxBackend(CTX)
    pool = pool_for(be)
    scope = QueueScope(placement="chip")
    before = dict(_placement_decisions.snapshot())
    p = place_stream(be, "foreground", scope=scope, cost_hint=1)
    p.close()
    after = _placement_decisions.snapshot()
    assert after.get(("ledger",), 0) == before.get(("ledger",), 0) + 1
    q0 = scope.for_backend(pool.chip_backend(0))
    with q0.admission("foreground", 999):
        p = place_stream(be, "foreground", scope=scope, cost_hint=1)
        p.close()
    assert _placement_decisions.snapshot().get(("live",), 0) == (
        before.get(("live",), 0) + 1
    )
