"""Headline benchmark: RS 10+4 erasure-coding, kernel AND end-to-end.

Mirrors the reference's hot loop (weed/storage/erasure_coding/ec_encoder.go
encodeDataOneBatch: klauspost/reedsolomon SIMD GF(2^8) encode) against this
framework's device path (XLA/Pallas bit-matmul encode, seaweedfs_tpu/ops),
and BASELINE.json configs 1-2 end-to-end: `ec.encode` of a fabricated
volume disk->shards+.ecsum, and a 2-shard `ec.rebuild`.

Headline (ISSUE 10 / ROADMAP direction 1): ec_encode_e2e — the
end-to-end disk->shards encode on the zero-copy NATIVE data plane
(native batched reads + fused write+CRC sink, ec/native_io.py), with
the pure-Python byte path re-measured on the same volume as the
vs_baseline denominator and bit-identity (shard + v2 leaf CRCs)
asserted in-line. The kernel-only and disk-independent-pipeline
figures remain as sub-fields (kernel_gbs / pipeline_gbs) — context,
never the headline.

Self-verification (every device number is evidence, not vibes):
- the kernel loop encodes a DIFFERENT pre-staged buffer each rep, and every
  device output is CRC-checked against the C++ AVX2 encoder's result;
- a physical-consistency guard flags any kernel rate whose implied HBM
  traffic exceeds the chip's bandwidth (a broken block_until_ready cannot
  produce a "valid" number);
- the end-to-end device encode must reproduce the CPU run's .ecsum shard
  CRCs bit-exactly, and the rebuild re-verifies against the sidecar.

Baseline = the C++ AVX2 PSHUFB encoder (native/seaweed_native.cpp), the same
nibble-table technique klauspost uses on amd64, multi-threaded across all
host cores (ctypes releases the GIL). vs_baseline = device / CPU end-to-end.

Prints exactly ONE JSON line, e.g.:
  {"metric": "ec_encode_e2e_10p4[...]", "value": N, "unit": "GB/s",
   "vs_baseline": N, "kernel_gbs": ..., "kernel_verified": true, ...}
"""

from __future__ import annotations

import json
import os
import shutil
import struct
import sys
import tempfile
import threading
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K, M = 10, 4
BLOCK = 32 << 20  # bytes per data shard => 320 MiB data per kernel pass
SMALL_WIDTH = 1 << 22  # first-landing kernel stage: seconds, not minutes
REPS = 3  # distinct input buffers, one per timed rep
SEEDS = [0x5EAD + i for i in range(REPS)]
# slice widths a kernel stage may use (CPU truth precomputed for each)
VERIFY_WIDTHS = [1 << 20, SMALL_WIDTH, 1 << 23, BLOCK]

# Advertised HBM bandwidth ceilings (GB/s) by device_kind substring.
# Used only to flag IMPOSSIBLE numbers, not to grade real ones.
_HBM_GBS = [
    ("v6e", 1640), ("v6 lite", 1640), ("v5p", 2765), ("v5e", 819),
    ("v5 lite", 819), ("v4", 1228), ("v3", 900), ("v2", 700),
]


def _hbm_ceiling(kind: str) -> float:
    k = kind.lower()
    for sub, gbs in _HBM_GBS:
        if sub in k:
            return float(gbs)
    raise KeyError(
        f"device_kind {kind!r} is not in the HBM table (_HBM_GBS): add "
        f"its published peak, a device that is not in the table is an "
        f"error, not a default"
    )


def _gen(seed: int, width: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, 256, size=(K, width), dtype=np.uint8
    )


def _crc_rows(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


# --------------------------------------------------------------------------
# CPU phase (parent process)
# --------------------------------------------------------------------------

def _cpu_kernel_gbs(data: np.ndarray, coeffs: np.ndarray, threads: int) -> float:
    """Multi-threaded native AVX2 encode throughput (data bytes / s)."""
    from seaweedfs_tpu.utils import native

    n = data.shape[1]
    chunk = max(1 << 20, n // max(threads, 1))
    chunks = [
        np.ascontiguousarray(data[:, lo : min(lo + chunk, n)])
        for lo in range(0, n, chunk)
    ]

    def run_chunk(c):
        native.rs_apply(coeffs, c)

    with ThreadPoolExecutor(max_workers=threads) as ex:
        list(ex.map(run_chunk, chunks))  # warmup (tables + page-in)
        t0 = time.perf_counter()
        for _ in range(REPS):
            list(ex.map(run_chunk, chunks))
        dt = (time.perf_counter() - t0) / REPS
    return data.nbytes / dt / 1e9


def _expected_kernel_crcs(coeffs: np.ndarray) -> dict[str, dict[str, int]]:
    """CPU-truth parity CRCs per (seed, width). A (K, w) buffer is NOT a
    column-prefix of the (K, BLOCK) buffer for the same seed (the RNG
    fills row-major), so each width the device phase might pick is
    generated and encoded at that exact width."""
    from seaweedfs_tpu.utils import native

    out: dict[str, dict[str, int]] = {}
    for seed in SEEDS:
        out[str(seed)] = {
            str(w): _crc_rows(native.rs_apply(coeffs, _gen(seed, w)))
            for w in VERIFY_WIDTHS
        }
    return out


def _fabricate_volume(base_dir: str, target_bytes: int) -> str:
    """Create a real .dat/.idx volume of >= target_bytes; returns base path."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    vol = Volume(base_dir, 1, needle_map_kind="memory")
    rng = np.random.default_rng(0xB0B)
    blob = rng.integers(0, 256, size=1 << 20, dtype=np.uint8).tobytes()
    nid = 1
    while vol.size < target_bytes:
        # vary content so shards aren't trivially compressible/repetitive
        n = Needle(cookie=0x1234, needle_id=nid, data=blob[nid % 1024 :] + blob[: nid % 1024])
        vol.write_needle(n)
        nid += 1
    vol.flush()
    base = vol.base_file_name(base_dir, "", 1)
    vol.close()
    return base


def _clear_shards(base: str) -> None:
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT

    for i in range(DEFAULT_EC_CONTEXT.total):
        p = base + DEFAULT_EC_CONTEXT.to_ext(i)
        if os.path.exists(p):
            os.unlink(p)
    for ext in (".ecx", ".ecsum", ".vif"):
        if os.path.exists(base + ext):
            os.unlink(base + ext)


def _cpu_e2e(
    base: str, force_python: bool = False
) -> tuple[float, list[list[int]], int]:
    """Timed CPU disk->shards encode; returns (gbs, shard_crcs, dat_size).
    `force_python` pins the pure-Python source/sink plane
    (SEAWEED_EC_NATIVE=0) so the headline native-plane number ships with
    its own bit-identity evidence and speedup ratio."""
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.bitrot import BitrotProtection
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.encoder import ec_encode_volume

    dat_size = os.path.getsize(base + ".dat")
    prev = os.environ.get("SEAWEED_EC_NATIVE")
    if force_python:
        os.environ["SEAWEED_EC_NATIVE"] = "0"
    try:
        t0 = time.perf_counter()
        ec_encode_volume(base, backend=CpuBackend(DEFAULT_EC_CONTEXT))
        dt = time.perf_counter() - t0
    finally:
        if force_python:
            if prev is None:
                os.environ.pop("SEAWEED_EC_NATIVE", None)
            else:
                os.environ["SEAWEED_EC_NATIVE"] = prev
    prot = BitrotProtection.load(base + ".ecsum")
    return dat_size / dt / 1e9, prot.shard_crcs, dat_size


def _cpu_rebuild_bench(base: str, dat_size: int) -> dict:
    """BASELINE config 2 on the CPU backend: rebuild 2 missing shards
    (one data, one parity), serial baseline vs the shared recovery
    pipeline, bit-identical outputs enforced both ways."""
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.bitrot import BitrotProtection, ShardChecksumBuilder
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.rebuild import rebuild_ec_files

    ctx = DEFAULT_EC_CONTEXT
    backend = CpuBackend(ctx)
    prot = BitrotProtection.load(base + ".ecsum")
    missing = [1, K + 1]
    batch = 16 << 20

    # --- serial baseline: the pre-pipeline implementation in full —
    # upfront whole-shard sidecar verify of every present shard, then a
    # strictly sequential read -> reconstruct -> write loop with
    # Python-side CRC + tobytes per batch. Runs against temp outputs
    # with the missing shards simulated so the volume is untouched.
    present = [
        i
        for i in range(ctx.total)
        if i not in missing and os.path.exists(base + ctx.to_ext(i))
    ]
    t_verify0 = time.perf_counter()
    for i in present:
        prot.verify_shard_file(base + ctx.to_ext(i), i)
    serial_verify_dt = time.perf_counter() - t_verify0
    src = sorted(present)[: ctx.data_shards]
    shard_size = os.path.getsize(base + ctx.to_ext(src[0]))
    tmp_paths = {i: base + ctx.to_ext(i) + ".serialbench" for i in missing}
    serial_ok = True

    def serial_once() -> float:
        nonlocal serial_ok
        fds = {i: os.open(base + ctx.to_ext(i), os.O_RDONLY) for i in src}
        outs = {i: open(p, "wb") for i, p in tmp_paths.items()}
        builders = {i: ShardChecksumBuilder(prot.block_size) for i in missing}
        t0 = time.perf_counter()
        try:
            for off in range(0, shard_size, batch):
                width = min(batch, shard_size - off)
                block = {
                    i: np.frombuffer(os.pread(fds[i], width, off), dtype=np.uint8)
                    for i in src
                }
                rec = backend.reconstruct(block, want=missing)
                for i in missing:
                    b = np.asarray(rec[i], dtype=np.uint8).tobytes()
                    outs[i].write(b)
                    builders[i].write(b)
            for f in outs.values():
                f.flush()
                os.fsync(f.fileno())
        finally:
            for fd in fds.values():
                os.close(fd)
            for f in outs.values():
                f.close()
        dt = time.perf_counter() - t0
        serial_ok = serial_ok and all(
            builders[i].total == prot.shard_sizes[i]
            and builders[i].finish() == prot.shard_crcs[i]
            for i in missing
        )
        return dt

    # Best-of-2, matching the warm best-of-N treatment the pipelined and
    # staged variants get below — all three numbers are page-cache-warm
    # floors, so the ratios compare algorithms, not cache states.
    serial_dt = min(serial_once(), serial_once()) + serial_verify_dt
    for p in tmp_paths.values():
        os.unlink(p)

    # --- pipelined (PR 2 shape, synchronous apply) vs staged (PR 3,
    # async H2D/compute/D2H through the backend staging hooks): actually
    # lose the shards, rebuild_ec_files them back (publishes
    # temp+fsync+rename, sidecar-verified), compare bit-for-bit against
    # the originals. Two timed reps per variant, best-of: the variants
    # do IDENTICAL I/O and GF math on CPU, so min-dt is the honest
    # comparison (staged must be parity-not-regression here; the
    # overlap win only exists where D2H actually blocks — on a device).
    originals = {}
    for i in missing:
        with open(base + ctx.to_ext(i), "rb") as f:
            originals[i] = f.read()

    identical = True

    def one_rebuild(staged: bool) -> float:
        nonlocal identical
        for i in missing:
            if os.path.exists(base + ctx.to_ext(i)):
                os.unlink(base + ctx.to_ext(i))
        t0 = time.perf_counter()
        rebuilt = rebuild_ec_files(base, backend=backend, staged=staged)
        dt = time.perf_counter() - t0
        if sorted(rebuilt) != sorted(missing):
            identical = False
        for i in missing:
            with open(base + ctx.to_ext(i), "rb") as f:
                if f.read() != originals[i]:
                    identical = False
        return dt

    # Interleaved best-of-3 after a warmup (page cache + fsync drift
    # dominate at small volume sizes; interleaving decorrelates it and
    # min-of-N converges both variants to their I/O floor).
    one_rebuild(staged=True)
    times = {False: float("inf"), True: float("inf")}
    for _ in range(3):
        for staged in (False, True):
            times[staged] = min(times[staged], one_rebuild(staged))
    pipe_dt, staged_dt = times[False], times[True]
    return {
        "rebuild_serial_gbs": round(dat_size / serial_dt / 1e9, 3),
        "rebuild_pipeline_gbs": round(dat_size / pipe_dt / 1e9, 3),
        "rebuild_staged_gbs": round(dat_size / staged_dt / 1e9, 3),
        "rebuild_vs_serial": round(serial_dt / pipe_dt, 3),
        "rebuild_staged_vs_sync": round(pipe_dt / staged_dt, 3),
        "rebuild_bit_identical": bool(serial_ok and identical),
    }


def _colocated_bench(
    batch: int = 1 << 20, fg_batches: int = 48, reps: int = 3
) -> dict:
    """encode_vs_rebuild_colocated: foreground encode throughput with
    and without a concurrent saturating recovery stream multiplexed on
    the SAME device queue, interleaved best-of-N (isolated/colocated
    alternate so drift hits both variants equally).

    Runs on the CPU backend through a private DeviceQueue with window=1
    so admission order IS the compute schedule (on a real chip the
    device serializes compute the same way): the ratio measures the
    scheduler's priority policy — foreground keeps >= (1 - recovery
    share) of the chip while the recovery stream keeps a non-zero
    batches/s floor (the no-starvation guarantee), instead of the two
    streams fighting or serializing FIFO."""
    import threading as _threading

    from seaweedfs_tpu.ec.backend import CpuBackend, _decode_coeffs
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.device_queue import DeviceQueue
    from seaweedfs_tpu.ops import gf256

    ctx = DEFAULT_EC_CONTEXT
    k = ctx.data_shards
    be = CpuBackend(ctx)
    q = DeviceQueue(window=1)
    rng = np.random.default_rng(0xC0)
    data = rng.integers(0, 256, (k, batch), dtype=np.uint8)
    rs = gf256.ReedSolomon(k, ctx.parity_shards)
    rec_coeffs = _decode_coeffs(
        rs.matrix, k, (0, 1), tuple(range(2, 2 + k))
    )

    from seaweedfs_tpu.ec.device_queue import batch_cost

    fg_cost = batch_cost(ctx.parity_shards, batch)  # encode: m rows out
    rec_cost = batch_cost(rec_coeffs.shape[0], batch)

    def fg_pass() -> float:
        # Same two-thread shape as the production encoder (dispatch in
        # the calling thread, to_host+release in a drain thread behind a
        # bounded queue): the NEXT batch's admission request is queued
        # before the current slot releases, so the scheduler sees a
        # continuous foreground stream — a serial dispatch/drain loop
        # would hand every released slot to the work-conserving
        # recovery class and measure the loop's own gaps, not the
        # policy.
        import queue as _q

        s = q.stream("foreground", "bench encode")
        outq: "_q.Queue" = _q.Queue(maxsize=2)
        drain_errors: list = []

        def drain():
            try:
                while True:
                    item = outq.get()
                    if item is None:
                        return
                    t, h = item
                    try:
                        np.asarray(be.to_host(h))
                    finally:
                        s.release(t)
            except BaseException as e:  # noqa: BLE001
                # Keep draining (releasing window slots!) so the
                # producer's bounded put and its next admission never
                # block against a dead consumer (the same discipline as
                # run_pipeline's writer) — the error resurfaces in the
                # producer below.
                drain_errors.append(e)
                while True:
                    item = outq.get()
                    if item is None:
                        return
                    s.release(item[0])

        th = _threading.Thread(target=drain, daemon=True)
        t0 = time.perf_counter()
        th.start()
        try:
            for _ in range(fg_batches):
                t, h = s.dispatch(
                    lambda: be.encode_staged(be.to_device(data)), fg_cost
                )
                outq.put((t, h))
        finally:
            outq.put(None)
            th.join(timeout=60)
            s.close()
        if drain_errors:
            raise drain_errors[0]
        return (k * batch * fg_batches) / (time.perf_counter() - t0) / 1e9

    progress = {"batches": 0}
    stop = _threading.Event()

    def recovery_loop():
        s = q.stream("recovery", "bench rebuild")
        try:
            while not stop.is_set():
                t, h = s.dispatch(
                    lambda: be.apply_staged(rec_coeffs, be.to_device(data)),
                    rec_cost,
                )
                np.asarray(be.to_host(h))
                s.release(t)
                progress["batches"] += 1
        finally:
            s.close()

    fg_pass()  # warmup (page faults, allocator, coeff caches)
    iso, colo, rec_rates = [], [], []
    for _ in range(reps):
        iso.append(fg_pass())
        stop.clear()
        th = _threading.Thread(target=recovery_loop, daemon=True)
        th.start()
        time.sleep(0.05)  # let the recovery stream saturate first
        progress["batches"] = 0
        t0 = time.perf_counter()
        colo.append(fg_pass())
        dt = time.perf_counter() - t0
        stop.set()
        th.join(timeout=30)
        rec_rates.append(progress["batches"] / max(dt, 1e-9))
    best_iso, best_colo = max(iso), max(colo)
    return {
        # acceptance bar: >= 0.85 with colocated_recovery_bps > 0
        "encode_vs_rebuild_colocated": round(best_colo / best_iso, 3),
        "colocated_fg_gbs": round(best_colo, 3),
        "isolated_fg_gbs": round(best_iso, 3),
        "colocated_recovery_bps": round(min(rec_rates), 2),
    }


def _placement_bench(
    n_streams: int | None = None,
    batch: int | None = None,
    batches: int | None = None,
    reps: int = 3,
) -> dict:
    """multi_stream_placement: aggregate throughput of N concurrent
    encode streams on an emulated 8-device host, whole-stream chip
    placement (ec/chip_pool.py) vs the PR 4 mesh-sliced baseline where
    every stream is column-sliced across all 8 devices and serializes
    behind one admission queue.

    Shape: each stream runs the production encoder's two-thread
    pipeline over `batches` encode batches, rotating through 3
    DISTINCT input buffers (defeats any transfer caching; same trick
    as the kernel loop): the dispatch thread stages H2D + device
    dispatch under queue admission, and the drain thread does
    to_host -> release the window slot -> consume (CRC-verify the
    parity against the CPU truth for that buffer) — exactly
    run_staged_apply's writer discipline, consumer work AFTER the slot
    frees. Every drained parity of every pass is verified, so
    bit-identical outputs per stream is part of the metric, not an
    afterthought. Variants alternate (interleaved best-of-N) so load
    drift hits both equally.

    Shape note: the default batch width (1 KiB per shard = a ~10 KiB
    extent at 10+4) is the SERVING-stream shape — the high-concurrency
    traffic the placement layer exists for is degraded reads and
    small-volume encodes (PR 2/3 reconstruct leaf- and needle-sized
    extents), where per-batch compute is comparable to per-batch
    dispatch cost, exactly as on real TPUs where a 16 MiB batch
    computes in ~100 us against ~50-100 us of per-chip dispatch. Bulk
    lone-stream encodes (16 MiB batches) are the case `ec_placement=
    auto` deliberately LEAVES on the mesh, so they are not this
    metric; the SEAWEED_BENCH_PLACEMENT_* env knobs re-measure any
    other shape. On the mesh baseline every batch pays 8-way sharded
    H2D, shard_map dispatch, and gathered D2H, and all streams share
    ONE admission window; real pods add the parallel-chip compute win
    this 2-core emulation cannot show. Hermetic: the stage child
    forces the 8-device virtual CPU platform — no TPU, no disk."""
    import threading as _threading

    from seaweedfs_tpu.ec.backend import CpuBackend, JaxBackend
    from seaweedfs_tpu.ec.chip_pool import place_stream, pool_for
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.device_queue import QueueScope, batch_cost

    n_streams = n_streams or int(
        os.environ.get("SEAWEED_BENCH_PLACEMENT_STREAMS", "4")
    )
    batch = batch or (
        int(os.environ.get("SEAWEED_BENCH_PLACEMENT_BATCH_KB", "1")) << 10
    )
    batches = batches or int(
        os.environ.get("SEAWEED_BENCH_PLACEMENT_BATCHES", "96")
    )
    ctx = DEFAULT_EC_CONTEXT
    be = JaxBackend(ctx)  # 8 virtual devices -> column mesh
    pool = pool_for(be)
    if pool is None:
        return {"error": "no chip pool (forced 8-device platform missing?)"}
    cpu = CpuBackend(ctx)
    NBUF = 3
    datas = [
        [_gen(0x9A0 + i * NBUF + j, batch) for j in range(NBUF)]
        for i in range(n_streams)
    ]
    expected = [
        [zlib.crc32(np.ascontiguousarray(cpu.encode(d)).tobytes()) for d in row]
        for row in datas
    ]
    m = ctx.parity_shards

    def stream_worker(scope, i, oks, errors, barrier):
        # Same two-thread shape as the production encoder: dispatch in
        # this thread, to_host+release in a drain thread behind a
        # bounded queue. NEVER block on the next admission while
        # holding an undrained ticket in the same thread — on a shared
        # (mesh-baseline) queue four such streams would hold every
        # window slot and deadlock each other.
        import queue as _q

        placement = None
        s = None
        # Depth 3 (+1 being drained) matches one chip's window=4: a
        # PLACED stream can keep its whole chip window full, while the
        # mesh baseline's streams share ONE window-4 queue — the
        # pod-serialization this metric exists to expose.
        outq: "_q.Queue" = _q.Queue(maxsize=3)
        ok = True

        def drain():
            nonlocal ok
            while True:
                item = outq.get()
                if item is None:
                    return
                t, h, j = item
                try:
                    parity = np.ascontiguousarray(
                        placement.backend.to_host(h), dtype=np.uint8
                    )
                except BaseException:  # noqa: BLE001
                    ok = False
                    s.release(t)
                    continue
                # production writer discipline: the slot frees the
                # moment the result is on the host; the consumer work
                # (here: CRC verification, in the encoder: fused
                # write+CRC) runs after, backpressuring only THIS
                # stream's drain.
                s.release(t)
                if zlib.crc32(parity.tobytes()) != expected[i][j]:
                    ok = False

        th = None
        try:
            placement = place_stream(
                be, "foreground", scope=scope,
                cost_hint=batch_cost(m, batch * batches),
            )
            s = placement.queue.stream("foreground", f"bench stream {i}")
            th = _threading.Thread(target=drain, daemon=True)
            th.start()
            barrier.wait(timeout=60)
            for b in range(batches):
                j = b % NBUF
                t, h = s.dispatch(
                    lambda j=j: placement.backend.encode_staged(
                        placement.backend.to_device(datas[i][j])
                    ),
                    batch_cost(m, batch),
                )
                outq.put((t, h, j))
            outq.put(None)
            th.join(timeout=240)
            oks[i] = ok and not th.is_alive()
        except BaseException as e:  # noqa: BLE001 — the failure is evidence
            errors.append(repr(e)[:300])
            # A worker dying before its barrier.wait would leave the
            # siblings (and the timer) blocked for the full barrier
            # timeout with no recorded cause; abort unblocks everyone
            # and the captured error becomes the pass's verdict.
            barrier.abort()
            outq.put(None)
        finally:
            if s is not None:
                s.close()
            if placement is not None:
                placement.close()

    def one_pass(mode: str) -> tuple[float, bool]:
        scope = QueueScope(placement=mode)
        oks = [False] * n_streams
        errors: list = []
        barrier = _threading.Barrier(n_streams + 1)
        ts = [
            _threading.Thread(
                target=stream_worker,
                args=(scope, i, oks, errors, barrier),
            )
            for i in range(n_streams)
        ]
        for t in ts:
            t.start()
        try:
            barrier.wait(timeout=60)
        except _threading.BrokenBarrierError:
            for t in ts:
                t.join(timeout=30)
            raise RuntimeError(f"placement stream failed: {errors}")
        t0 = time.perf_counter()
        for t in ts:
            t.join(timeout=240)
        dt = time.perf_counter() - t0
        if errors or any(t.is_alive() for t in ts):
            raise RuntimeError(f"placement stream failed: {errors or 'wedged'}")
        gbs = (n_streams * K * batch * batches) / dt / 1e9
        return gbs, all(oks)

    # Warmup passes compile both shapes (mesh shard_map encode AND
    # per-chip encode) so the timed passes compare steady state; every
    # pass, warm or timed, verifies every parity.
    _, ok_mesh = one_pass("mesh")
    _, ok_chip = one_pass("chip")
    verified = ok_mesh and ok_chip
    best = {"mesh": 0.0, "chip": 0.0}
    for _ in range(reps):
        for mode in ("mesh", "chip"):
            gbs, ok = one_pass(mode)
            best[mode] = max(best[mode], gbs)
            verified = verified and ok
    return {
        # acceptance bar: >= 2.0 at 4 streams on the emulated 8-dev host
        "multi_stream_placement": round(best["chip"] / max(best["mesh"], 1e-9), 3),
        "placed_agg_gbs": round(best["chip"], 4),
        "mesh_agg_gbs": round(best["mesh"], 4),
        "placement_verified": bool(verified),
        "placement_streams": n_streams,
        "placement_chips": pool.n_chips,
        "placement_batch": batch,
        "placement_batches": batches,
    }


def _streaming_encode_bench(
    workdir: str,
    n_appends: int = 3000,
    append_bytes: int = 8192,
    flush_kib: int = 256,
    naive_segment_mb: int = 4,
) -> dict:
    """streaming_encode (ISSUE 14 acceptance metric): sustained append
    load through the online EC encoder vs the naive seal-then-batch-
    encode baseline IN THE SAME RUN, on the same bytes.

    Streaming: every append buffers into an `EcStreamEncoder`; a flush
    (pending >= flush threshold, plus a final one) runs the incremental
    parity math, pwrites, fsyncs, and advances the stripe-cursor
    journal — each append's time-to-durable-parity is the wall time
    from its append() to the flush that covered it.

    Naive: the same appends accumulate in a plain segment file; at
    every `naive_segment_mb` boundary the segment SEALS and
    `write_ec_files` batch-encodes it (fsync'd) — each append's
    time-to-durable-parity is the wall time to the END of its
    segment's encode, the seal-then-encode lag this PR removes.

    stream_vs_batch_identical: the streaming encoder's finalized
    shards + sidecar CRCs must be byte-equal to ONE batch encode over
    the concatenation (the RS-linearity identity, asserted in the
    line)."""
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.context import ECContext
    from seaweedfs_tpu.ec.encoder import write_ec_files
    from seaweedfs_tpu.ec.stream_encode import EcStreamEncoder

    ctx = ECContext(10, 4)
    be = CpuBackend(ctx)
    block = 256 * 1024
    small = 64 * 1024
    flush_bytes = flush_kib << 10
    rng = np.random.default_rng(0x57E4)
    payload = rng.integers(
        0, 256, n_appends * append_bytes, dtype=np.uint8
    ).tobytes()

    sdir = os.path.join(workdir, "stream_bench")
    os.makedirs(sdir, exist_ok=True)

    def quantiles(lags_ms: list[float]) -> tuple[float, float]:
        s = sorted(lags_ms)
        return (
            s[int(0.50 * (len(s) - 1))],
            s[int(0.99 * (len(s) - 1))],
        )

    # ---- streaming phase ------------------------------------------------
    sbase = os.path.join(sdir, "stream")
    enc = EcStreamEncoder(
        sbase, ctx, backend=be, block_size=block, small_block_size=small
    )
    t_append: list[float] = [0.0] * n_appends
    lags_ms: list[float] = []
    covered = 0
    t0 = time.perf_counter()
    for i in range(n_appends):
        t_append[i] = time.perf_counter()
        enc.append(payload[i * append_bytes : (i + 1) * append_bytes])
        if enc.pending_bytes >= flush_bytes:
            durable = enc.flush()
            now = time.perf_counter()
            while (covered + 1) * append_bytes <= durable:
                lags_ms.append((now - t_append[covered]) * 1e3)
                covered += 1
    durable = enc.flush()
    now = time.perf_counter()
    while covered < n_appends and (covered + 1) * append_bytes <= durable:
        lags_ms.append((now - t_append[covered]) * 1e3)
        covered += 1
    stream_wall = time.perf_counter() - t0
    prot_stream = enc.close()
    p50, p99 = quantiles(lags_ms)

    # ---- naive seal-then-encode phase ----------------------------------
    seg_bytes = naive_segment_mb << 20
    nbase_dir = os.path.join(sdir, "naive")
    os.makedirs(nbase_dir, exist_ok=True)
    naive_lags_ms: list[float] = []
    t0 = time.perf_counter()
    seg_start = 0  # first append index of the open segment
    seg_file = None
    seg = 0
    nt_append: list[float] = [0.0] * n_appends
    for i in range(n_appends):
        if seg_file is None:
            seg_file = open(
                os.path.join(nbase_dir, f"seg{seg:04d}.dat"), "wb"
            )
        nt_append[i] = time.perf_counter()
        seg_file.write(payload[i * append_bytes : (i + 1) * append_bytes])
        if seg_file.tell() >= seg_bytes or i == n_appends - 1:
            seg_file.flush()
            os.fsync(seg_file.fileno())
            seg_file.close()
            write_ec_files(
                os.path.join(nbase_dir, f"seg{seg:04d}"), ctx, be,
                large_block_size=block, small_block_size=small,
            )
            now = time.perf_counter()
            naive_lags_ms.extend(
                (now - nt_append[j]) * 1e3 for j in range(seg_start, i + 1)
            )
            seg_start = i + 1
            seg += 1
            seg_file = None
    naive_wall = time.perf_counter() - t0
    np50, np99 = quantiles(naive_lags_ms)

    # ---- identity: stream shards == ONE batch encode over the concat ---
    bbase = os.path.join(sdir, "batch")
    with open(bbase + ".dat", "wb") as f:
        f.write(payload)
    prot_batch = write_ec_files(
        bbase, ctx, be, large_block_size=block, small_block_size=small
    )
    identical = bool(
        prot_stream is not None
        and prot_stream.shard_crcs == prot_batch.shard_crcs
        and prot_stream.shard_leaf_crcs == prot_batch.shard_leaf_crcs
        and prot_stream.shard_sizes == prot_batch.shard_sizes
        and all(
            open(sbase + ctx.to_ext(i), "rb").read()
            == open(bbase + ctx.to_ext(i), "rb").read()
            for i in range(ctx.total)
        )
    )
    return {
        "time_to_durable_parity_p50_ms": round(p50, 3),
        "time_to_durable_parity_p99_ms": round(p99, 3),
        "streaming_appends_per_s": round(n_appends / stream_wall, 1),
        "streaming_parity_covered": covered,
        "naive_parity_p50_ms": round(np50, 3),
        "naive_parity_p99_ms": round(np99, 3),
        "naive_appends_per_s": round(n_appends / naive_wall, 1),
        "streaming_vs_naive_p99": round(np99 / max(p99, 1e-9), 2),
        "stream_vs_batch_identical": identical,
        "streaming_append_kib": append_bytes >> 10,
        "streaming_flush_kib": flush_kib,
        "naive_segment_mb": naive_segment_mb,
    }


def _leaf_repair_bench(base: str) -> dict:
    """Leaf repair vs full-shard rebuild (ISSUE 8 acceptance metric):
    one rotten 64 KiB leaf in one shard, fixed two ways against the
    same volume — (a) leaf-granular in-place repair under the repair
    journal (~k leaves of sibling I/O), (b) whole-shard rebuild (~k
    shards). Reports bytes moved + wall time for both, asserts both
    outcomes are byte-identical to the original shard."""
    from seaweedfs_tpu.ec.bitrot import BitrotProtection
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.rebuild import rebuild_ec_files
    from seaweedfs_tpu.ec.repair_journal import (
        apply_leaf_repair,
        leaf_verdict,
        reconstruct_leaves,
    )

    ctx = DEFAULT_EC_CONTEXT
    be = CpuBackend(ctx)
    prot = BitrotProtection.load(base + ".ecsum")
    victim = 1
    path = base + ctx.to_ext(victim)
    with open(path, "rb") as f:
        original = f.read()

    # rot one leaf in the middle of the shard
    leaf = min(len(prot.shard_leaf_crcs[victim]) - 1, 3)
    with open(path, "r+b") as f:
        f.seek(leaf * prot.leaf_size + 17)
        f.write(b"\x5a\xa5\x5a")

    moved = [0]

    def read_range(sid: int, lo: int, size: int) -> bytes | None:
        try:
            with open(base + ctx.to_ext(sid), "rb") as f:
                f.seek(lo)
                return f.read(size)
        except OSError:
            return None

    candidates = [i for i in range(ctx.total) if i != victim]
    t0 = time.perf_counter()
    bad = leaf_verdict(path, victim, prot)
    patches = reconstruct_leaves(
        prot, ctx, victim, bad, read_range, candidates, backend=be,
        on_bytes=lambda n: moved.__setitem__(0, moved[0] + n),
    )
    apply_leaf_repair(path, victim, prot, patches)
    leaf_repair_s = time.perf_counter() - t0
    leaf_repair_bytes = moved[0] + sum(len(p.data) for p in patches)
    with open(path, "rb") as f:
        repaired = f.read()

    # whole-shard rebuild of the same shard (bytes moved: k source
    # shards read + the regenerated shard written)
    os.unlink(path)
    t0 = time.perf_counter()
    rebuilt = rebuild_ec_files(base, ctx, backend=be)
    full_rebuild_s = time.perf_counter() - t0
    full_rebuild_bytes = (ctx.data_shards + 1) * len(original)
    with open(path, "rb") as f:
        rebuilt_bytes_disk = f.read()

    assert rebuilt == [victim]
    bit_identical = repaired == original and rebuilt_bytes_disk == original
    return {
        "leaf_repair_vs_full_rebuild": round(
            full_rebuild_bytes / max(leaf_repair_bytes, 1), 1
        ),
        "leaf_repair_bytes": leaf_repair_bytes,
        "full_rebuild_bytes": full_rebuild_bytes,
        "leaf_repair_s": round(leaf_repair_s, 4),
        "full_rebuild_s": round(full_rebuild_s, 4),
        "leaf_repair_bit_identical": bool(bit_identical),
    }


def _degraded_read_bench(base: str, n_reads: int = 12) -> dict:
    """BASELINE config 4: random needle reads with one data shard lost.
    Measures VERIFIED bytes-read amplification (sibling bytes fetched /
    needle bytes served) on the v2 leaf sidecar vs the same shards
    under a v1 (block-only) sidecar, plus the reconstructed-interval
    cache's effect on repeat reads. Correctness: every payload is
    checked against the fabricated volume's deterministic content."""
    from dataclasses import replace

    from seaweedfs_tpu.ec.bitrot import BitrotProtection
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.ec_volume import EcVolume
    from seaweedfs_tpu.ec.locate import locate_data
    from seaweedfs_tpu.storage.types import actual_offset

    ctx = DEFAULT_EC_CONTEXT
    directory = os.path.dirname(base)
    prot_v2 = BitrotProtection.load(base + ".ecsum")
    lost = 0
    shard_path = base + ctx.to_ext(lost)
    with open(shard_path, "rb") as f:
        saved_shard = f.read()
    os.unlink(shard_path)

    # the fabricated volume's deterministic payloads (see _fabricate_volume)
    blob = np.random.default_rng(0xB0B).integers(
        0, 256, size=1 << 20, dtype=np.uint8
    ).tobytes()

    def expected(nid: int) -> bytes:
        return blob[nid % 1024 :] + blob[: nid % 1024]

    def pick_needles(ev) -> list[int]:
        """Needle ids whose extents touch the lost shard (those are the
        degraded reads; others read straight from live shards)."""
        out = []
        nid = 1
        while len(out) < n_reads:
            nv = ev.find_needle(nid)
            if nv is None:
                break
            off = actual_offset(nv.offset)
            from seaweedfs_tpu.ec.decoder import record_actual_size

            rec = record_actual_size(nv.size, ev.version)
            ivs = locate_data(
                off, rec, ev._locate_shard_size, ctx.data_shards
            )
            if any(
                iv.to_shard_and_offset(ctx.data_shards)[0] == lost
                for iv in ivs
            ):
                out.append(nid)
            nid += 1
        return out

    def measure(cache_bytes: int) -> tuple[float, bool, float, "EcVolume"]:
        ev = EcVolume(
            directory, 1, backend_name="cpu",
            interval_cache_bytes=cache_bytes,
        )
        ids = pick_needles(ev)
        if not ids:
            ev.close()
            return 0.0, False, 0.0, ev
        ok = True
        served = 0
        b0 = ev.bytes_read
        t0 = time.perf_counter()
        for nid in ids:
            n = ev.read_needle(nid, cookie=0x1234)
            served += len(n.data)
            if n.data != expected(nid):
                ok = False
        dt = time.perf_counter() - t0
        amp = (ev.bytes_read - b0) / max(served, 1)
        return amp, ok, dt / len(ids), ev

    result: dict = {}
    try:
        # v2 sidecar (leaf-granular verify), cache off = raw amplification
        amp_v2, ok_v2, ms_v2, ev = measure(0)
        ev.close()
        # repeat-read behavior with the interval cache on
        ev = EcVolume(directory, 1, backend_name="cpu")
        ids = pick_needles(ev)
        for nid in ids:
            ev.read_needle(nid, cookie=0x1234)
        b_before = ev.bytes_read
        for nid in ids:
            ev.read_needle(nid, cookie=0x1234)
        cached_extra = ev.bytes_read - b_before
        ev.close()

        # v1 sidecar: same shards, leaves stripped — today's block-
        # granular behavior on identical data.
        replace(
            prot_v2, leaf_size=0, shard_leaf_crcs=[]
        ).save(base + ".ecsum")
        amp_v1, ok_v1, ms_v1, ev = measure(0)
        ev.close()
        result = {
            "degraded_amp_v1": round(amp_v1, 1),
            "degraded_amp_v2": round(amp_v2, 1),
            "degraded_amp_reduction": round(amp_v1 / max(amp_v2, 1e-9), 1),
            "degraded_read_ms_v1": round(ms_v1 * 1e3, 2),
            "degraded_read_ms_v2": round(ms_v2 * 1e3, 2),
            "degraded_verified": bool(ok_v1 and ok_v2),
            "degraded_cached_repeat_bytes": int(cached_extra),
        }
    finally:
        # restore the volume exactly: lost shard back, v2 sidecar back
        with open(shard_path, "wb") as f:
            f.write(saved_shard)
        prot_v2.save(base + ".ecsum")
    return result


def _bench_free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _gateway_client_phase(
    base: str,
    data: bytes,
    clients: int,
    reads_per_client: int,
    headers: dict | None = None,
) -> dict:
    """Fire `clients` concurrent keep-alive sessions, each doing
    `reads_per_client` byte-verified GETs; a threading.Barrier aligns
    the first wave so cold-cache misses genuinely collide. 503s are
    counted separately (clean backpressure, not corruption).
    `headers` (e.g. a SigV4 Authorization set) rides on every GET."""
    import threading

    import requests as _rq

    lat_lock = threading.Lock()
    latencies: list[float] = []
    errors = [0]
    rejected = [0]
    barrier = threading.Barrier(clients)

    def client() -> None:
        sess = _rq.Session()
        try:
            barrier.wait(timeout=30)
        except threading.BrokenBarrierError:
            pass
        for _ in range(reads_per_client):
            t0 = time.perf_counter()
            try:
                rr = sess.get(
                    f"{base}/bench/obj", timeout=120, headers=headers
                )
                if rr.status_code == 503:
                    with lat_lock:
                        rejected[0] += 1
                    continue
                ok = rr.status_code == 200 and rr.content == data
            except Exception:
                ok = False
            dt = time.perf_counter() - t0
            with lat_lock:
                if ok:
                    latencies.append(dt)
                else:
                    errors[0] += 1

    threads = [threading.Thread(target=client) for _ in range(clients)]
    t_all = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_all
    if not latencies:
        return {"error": "no successful GETs", "errors": errors[0]}
    lat_ms = np.array(sorted(latencies)) * 1e3
    return {
        "p50_ms": round(float(np.percentile(lat_ms, 50)), 2),
        "p99_ms": round(float(np.percentile(lat_ms, 99)), 2),
        "mean_ms": round(float(lat_ms.mean()), 2),
        "requests": len(latencies),
        "errors": errors[0],
        "rejected_503": rejected[0],
        "gets_per_s": round(len(latencies) / wall, 1),
    }


def _gateway_bench(
    workdir: str,
    clients: int = 100,
    reads_per_client: int = 5,
    naive_reads_per_client: int = 2,
    obj_bytes: int = 256 << 10,
) -> dict:
    """ISSUE 11 headline: p50/p99 S3 GET latency under `clients` (>=100)
    concurrent clients against a DEGRADED EC volume (one shard
    unmounted) over a real in-process cluster — real HTTP/gRPC on
    ephemeral ports, every payload byte-checked. TWO configurations in
    the same run:

    - NAIVE (the PR 9 baseline shape): unbounded one-thread-per-
      connection S3 front end, hot caches DISABLED (capacity 0 = no
      storage, no singleflight) — every GET pays the full
      reconstruction miss path;
    - TUNED: bounded worker-pool front ends + the tiered hot-chunk
      cache with singleflight collapse (first wave of misses collides
      on purpose via a start barrier and must collapse to one load per
      chunk, proven by the emitted singleflight counter).

    Published as gateway_degraded_get_{p50,p99,mean}_ms (tuned, the
    trended headline), gateway_naive_* (same-run baseline), and the
    gateway_singleflight_waits / gateway_hot_cache_* evidence."""
    import requests as _rq

    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.pb import cluster_pb2 as _cpb
    from seaweedfs_tpu.pb import rpc as _brpc
    from seaweedfs_tpu.s3 import S3Server
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellEnv, run_command
    from seaweedfs_tpu.storage.file_id import FileId

    import grpc as _grpc

    gdir = os.path.join(workdir, "gateway")
    os.makedirs(gdir, exist_ok=True)
    mport = _bench_free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[os.path.join(gdir, "v")],
        master=f"localhost:{mport}",
        ip="localhost",
        port=_bench_free_port(),
        ec_backend="cpu",
    )
    vs.start()
    filer = srv = srv_naive = env = None
    try:
        deadline = time.time() + 20
        while not master.topo.nodes:
            if time.time() > deadline:
                raise TimeoutError("volume server never registered")
            time.sleep(0.05)
        filer = Filer(
            MemoryStore(), master=f"localhost:{mport}",
            chunk_size=64 * 1024,
        )
        # tuned front end: bounded worker pool (the production shape)
        srv = S3Server(filer, ip="localhost", port=_bench_free_port())
        srv.start()
        # naive front end: the unbounded ThreadingHTTPServer baseline,
        # same filer/volume underneath
        srv_naive = S3Server(
            filer, ip="localhost", port=_bench_free_port(), http_workers=0
        )
        srv_naive.start()
        base = f"http://localhost:{srv.port}"
        base_naive = f"http://localhost:{srv_naive.port}"
        rng = np.random.default_rng(0x6A7E)
        data = rng.integers(0, 256, obj_bytes, dtype=np.uint8).tobytes()
        assert _rq.put(f"{base}/bench").status_code == 200
        assert _rq.put(f"{base}/bench/obj", data=data).status_code == 200

        entry = filer.find_entry("/buckets/bench/obj")
        vid = FileId.parse(entry.chunks[0].fid).volume_id
        env = ShellEnv(f"localhost:{mport}")
        out = run_command(env, f"ec.encode -volumeId {vid} -backend cpu")
        if "generation" not in out:
            raise RuntimeError(f"ec.encode failed: {out}")
        deadline = time.time() + 20
        while not any(
            vid in n.ec_shards for n in master.topo.nodes.values()
        ):
            if time.time() > deadline:
                raise TimeoutError("ec shards never registered")
            time.sleep(0.1)
        # quarantine one data shard: every GET touching its stripe is
        # now a verified degraded reconstruction on the volume server
        with _grpc.insecure_channel(f"localhost:{vs.grpc_port}") as ch:
            _brpc.volume_stub(ch).VolumeEcShardsUnmount(
                _cpb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[0])
            )
        r = _rq.get(f"{base}/bench/obj", timeout=120)
        if r.status_code != 200 or r.content != data:
            raise RuntimeError(
                f"warmup degraded GET failed: {r.status_code}"
            )

        chunk_cache = filer.chunk_cache
        interval_cache = vs.store.ec_interval_cache
        tuned_caps = (
            chunk_cache.capacity,
            interval_cache.capacity if interval_cache is not None else 0,
        )

        def set_caches(enabled: bool) -> None:
            chunk_cache.capacity = tuned_caps[0] if enabled else 0
            chunk_cache.clear()
            if interval_cache is not None:
                interval_cache.capacity = tuned_caps[1] if enabled else 0
                interval_cache.clear()

        # ---- NAIVE: caches off (capacity 0 = pass-through, no
        # singleflight), unbounded-thread front end — the miss path the
        # tiered cache exists to kill. Fewer reads per client: every
        # one pays a reconstruction.
        set_caches(False)
        naive = _gateway_client_phase(
            base_naive, data, clients, naive_reads_per_client
        )

        # ---- TUNED: caches restored and dropped ONCE, so the barrier-
        # aligned first wave is `clients` concurrent misses that must
        # singleflight-collapse; the rest ride the hot tier.
        set_caches(True)
        sf_before = (
            chunk_cache.singleflight_waits
            + (interval_cache.singleflight_waits if interval_cache else 0)
        )
        loads_before = chunk_cache.loads
        hits_before = chunk_cache.hits
        tuned = _gateway_client_phase(base, data, clients, reads_per_client)
        sf_waits = (
            chunk_cache.singleflight_waits
            + (interval_cache.singleflight_waits if interval_cache else 0)
            - sf_before
        )
        if "error" in tuned:
            return {"gateway_error": tuned["error"]}
        out = {
            "gateway_degraded_get_p50_ms": tuned["p50_ms"],
            "gateway_degraded_get_p99_ms": tuned["p99_ms"],
            "gateway_degraded_get_mean_ms": tuned["mean_ms"],
            "gateway_clients": clients,
            "gateway_requests": tuned["requests"],
            "gateway_errors": tuned["errors"],
            "gateway_rejected_503": tuned["rejected_503"],
            "gateway_object_kb": obj_bytes >> 10,
            "gateway_gets_per_s": tuned["gets_per_s"],
            # singleflight proof: the first wave's concurrent misses
            # joined in-flight loads instead of re-running them; the
            # chunk-load count stays ~#chunks, not #clients x #chunks
            "gateway_singleflight_waits": int(sf_waits),
            "gateway_hot_cache_loads": int(
                chunk_cache.loads - loads_before
            ),
            "gateway_hot_cache_hits": int(chunk_cache.hits - hits_before),
            "gateway_front_end": getattr(
                srv._http, "pool_status", lambda: {"kind": "threading"}
            )(),
        }
        if "error" not in naive:
            out.update(
                {
                    "gateway_naive_p50_ms": naive["p50_ms"],
                    "gateway_naive_p99_ms": naive["p99_ms"],
                    "gateway_naive_mean_ms": naive["mean_ms"],
                    "gateway_naive_gets_per_s": naive["gets_per_s"],
                    "gateway_naive_errors": naive["errors"],
                    "gateway_naive_requests": naive["requests"],
                    "gateway_p99_speedup_vs_naive": round(
                        naive["p99_ms"] / max(tuned["p99_ms"], 1e-9), 2
                    ),
                }
            )
        else:
            out["gateway_naive_error"] = naive["error"]
        return out
    finally:
        for closer in (
            (lambda: env.close()) if env is not None else None,
            (lambda: srv.stop()) if srv is not None else None,
            (lambda: srv_naive.stop()) if srv_naive is not None else None,
            (lambda: filer.close()) if filer is not None else None,
            vs.stop,
            master.stop,
        ):
            if closer is None:
                continue
            try:
                closer()
            except Exception:
                pass


def _net_counter_delta(
    before: dict, after: dict, plane: str, direction: str | None = None
) -> float:
    """Delta of one sw_net_bytes_* family for `plane`, summed across
    directions (or one direction when given) — keys are
    (plane, direction) label tuples."""

    def total(snap: dict) -> float:
        return sum(
            v for k, v in snap.items()
            if k and k[0] == plane
            and (direction is None or (len(k) > 1 and k[1] == direction))
        )

    return float(total(after) - total(before))


def _peer_rebuild_bench(workdir: str, shard_mb: int = 8, reps: int = 2) -> dict:
    """ISSUE 12 headline: peer-fetch rebuild throughput, NATIVE vs
    PYTHON network planes over the SAME loopback TCP wire in one run.

    The native plane is a real ShardNetPlane server (sendfile(2) shard
    egress) with `fetch_into` ingress landing streams straight into
    pooled aligned buffers, the granule CRC fused into the copy-in; the
    Python plane (SEAWEED_EC_NATIVE=0 for the whole run, so source,
    sink, AND wire are Python) moves the same bytes over the same
    socket through `bytes` materialization at every seam. Interleaved
    best-of-`reps`; the regenerated shard is asserted byte-identical
    across planes AND to the original (peer_rebuild_identical in the
    line). bytes_copied_per_byte_served per plane is derived from the
    sw_net_bytes_{copied,received}_total counters around each run —
    ~0.0 for the native plane is the zero-copy evidence."""
    import numpy as _np

    from seaweedfs_tpu.ec import net_plane as _netp
    from seaweedfs_tpu.ec.backend import CpuBackend as _Cpu
    from seaweedfs_tpu.ec.bitrot import (
        BitrotProtection as _BP,
        ShardChecksumBuilder as _Builder,
    )
    from seaweedfs_tpu.ec.context import ECContext as _Ctx
    from seaweedfs_tpu.ec.peer_rebuild import (
        PeerFetchTransient as _Transient,
        rebuild_from_peers as _rebuild,
    )
    from seaweedfs_tpu.utils import metrics as _M

    # tmpfs when available: the ≥1.2x native-vs-python target is a
    # byte-path number, not a disk benchmark
    root = "/dev/shm" if os.access("/dev/shm", os.W_OK) else workdir
    bdir = tempfile.mkdtemp(prefix="sw_peer_bench_", dir=root)
    ctx = _Ctx(4, 2)
    shard_bytes = shard_mb << 20
    generation = 7
    fds: dict = {}
    try:
        rng = _np.random.default_rng(0xBEEF)
        data = rng.integers(
            0, 256, (ctx.data_shards, shard_bytes), dtype=_np.uint8
        )
        shards = _np.concatenate([data, _Cpu(ctx).encode(data)], axis=0)
        builders = [
            _Builder(1 << 22, 64 * 1024) for _ in range(ctx.total)
        ]
        peer_dir = os.path.join(bdir, "peer")
        os.makedirs(peer_dir)
        fds = {}
        for i in range(ctx.total):
            blob = shards[i].tobytes()
            builders[i].write(blob)
            p = os.path.join(peer_dir, f"1{ctx.to_ext(i)}")
            with open(p, "wb") as f:
                f.write(blob)
            fds[i] = os.open(p, os.O_RDONLY)
        prot = _BP.from_builders(ctx, builders, generation=generation)

        def resolve(vid, sid, gen):
            if gen and gen != generation:
                raise _netp.NetPlaneError("stale generation")
            if sid not in fds:
                raise _netp.NetPlaneError("shard not local")
            return fds[sid], shard_bytes

        srv = _netp.ShardNetPlane(
            "127.0.0.1", 0, resolve, server_label="bench-peer"
        )
        srv.start()
        addr = ("127.0.0.1", srv.port)
        client = _netp.NetPlaneClient()

        def fetch(peer, sid, off, size):
            try:
                return client.read_bytes(addr, 1, sid, generation, off, size)
            except (_netp.NetPlaneError, _netp.NetPlaneUnavailable) as e:
                raise _Transient(str(e)) from e

        fetch_into = _netp.make_fetch_into(
            client, 1, generation, addr_of=lambda peer: addr
        )
        backend = _Cpu(ctx)
        # cluster-lost-holder bootstrap shape: NOTHING local but the
        # sidecar, every source crosses the wire — the configuration
        # this plane exists for (wire-dominated, k fetched streams).
        holders = {sid: ["peer"] for sid in range(ctx.data_shards + 1)}

        walls = {"native": [], "python": []}
        copied_per_served = {}
        rebuilt = {}
        prev_env = os.environ.get("SEAWEED_EC_NATIVE")
        try:
            for rep in range(reps):
                for plane in ("native", "python"):
                    ldir = os.path.join(bdir, f"{plane}{rep}")
                    os.makedirs(ldir)
                    base = os.path.join(ldir, "1")
                    prot.save(base + ".ecsum")
                    if plane == "python":
                        os.environ["SEAWEED_EC_NATIVE"] = "0"
                    else:
                        os.environ.pop("SEAWEED_EC_NATIVE", None)
                    cop0 = _M.net_bytes_copied_total.snapshot()
                    rec0 = _M.net_bytes_received_total.snapshot()
                    t0 = time.perf_counter()
                    rep_out = _rebuild(
                        base, holders, fetch, ctx=ctx, targets=[5],
                        backend=backend,
                        fetch_into=(
                            fetch_into if plane == "native" else None
                        ),
                    )
                    walls[plane].append(time.perf_counter() - t0)
                    cop1 = _M.net_bytes_copied_total.snapshot()
                    rec1 = _M.net_bytes_received_total.snapshot()
                    served = _net_counter_delta(rec0, rec1, plane)
                    copied = _net_counter_delta(cop0, cop1, plane)
                    if served > 0:
                        copied_per_served[plane] = round(copied / served, 2)
                    fetched_count = len(rep_out.fetched)
                    if rep_out.rebuilt != [5] or set(
                        rep_out.fetched_plane.values()
                    ) != {plane}:
                        return {
                            "peer_rebuild_error": (
                                f"{plane}: rebuilt={rep_out.rebuilt} "
                                f"planes={rep_out.fetched_plane}"
                            )
                        }
                    with open(base + ctx.to_ext(5), "rb") as f:
                        rebuilt[plane] = f.read()
        finally:
            if prev_env is None:
                os.environ.pop("SEAWEED_EC_NATIVE", None)
            else:
                os.environ["SEAWEED_EC_NATIVE"] = prev_env
            client.close()
            srv.stop()

        identical = (
            rebuilt["native"] == rebuilt["python"] == shards[5].tobytes()
        )
        # throughput denominator: sibling bytes moved over the wire
        wire = fetched_count * shard_bytes
        native_gbs = wire / min(walls["native"]) / 1e9
        python_gbs = wire / min(walls["python"]) / 1e9
        return {
            "peer_rebuild_gbs": round(native_gbs, 3),
            "peer_rebuild_python_gbs": round(python_gbs, 3),
            "peer_rebuild_native_vs_python": round(
                native_gbs / max(python_gbs, 1e-9), 2
            ),
            "peer_rebuild_identical": bool(identical),
            "peer_rebuild_wire_mb": wire >> 20,
            "peer_rebuild_staging": root,
            "bytes_copied_per_byte_served_native": copied_per_served.get(
                "native", 0.0
            ),
            "bytes_copied_per_byte_served_python": copied_per_served.get(
                "python", 0.0
            ),
        }
    finally:
        for fd in fds.values():
            try:
                os.close(fd)
            except OSError:
                pass
        shutil.rmtree(bdir, ignore_errors=True)


def _ec_rebalance_bench(
    workdir: str,
    payload_bytes: int = 1 << 20,
    reads_per_phase: int = 6,
    load_threads: int = 4,
) -> dict:
    """ISSUE 15 headline: degraded-read throughput BEFORE vs AFTER one
    data-gravity pass, in the same run, over a real in-process cluster.

    Shape: a skewed mini-cluster — the hot EC volume lives on node A,
    whose device queue is SATURATED by a competing admission load (the
    chip-poor/busy holder), while node B idles. B's heartbeat telemetry
    is shimmed to report 8 idle chips (this box has none — the same
    emulation discipline as the 8-virtual-device placement bench); A
    reports its real (chip-less, loaded) blob, and the volume HEAT
    counters are real bytes from the measured reads. The gravity pass
    is the PRODUCTION loop end to end: heartbeat telemetry -> master
    scan (`scan_for_ec_rebalance` -> plan_hot_migrations) -> ec_migrate
    task -> a real connected Worker -> `drive_migration` (net-plane
    copy, sidecar verify, unmount-then-mount). Evidence in the line:
    before/after reads-per-second, migrated-shard bit-identity, the
    exactly-one-mounted-holder invariant, and the migration's wire
    bytes attributed to the native plane
    (sw_net_bytes_received_total{plane=native})."""
    import hashlib

    import requests as _rq

    from seaweedfs_tpu.ec import native_io
    from seaweedfs_tpu.ec.device_queue import batch_cost
    from seaweedfs_tpu.pb import cluster_pb2 as _cpb
    from seaweedfs_tpu.pb import rpc as _brpc
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.shell.commands import ShellEnv, run_command
    from seaweedfs_tpu.storage.file_id import FileId
    from seaweedfs_tpu.utils import metrics as _M
    from seaweedfs_tpu.worker.worker import Worker

    import grpc as _grpc

    gdir = os.path.join(workdir, "rebalance")
    os.makedirs(gdir, exist_ok=True)
    mport = _bench_free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs_a = VolumeServer(
        directories=[os.path.join(gdir, "a")],
        master=f"localhost:{mport}", ip="localhost",
        port=_bench_free_port(), ec_backend="cpu",
        ec_interval_cache_mb=0,  # every degraded read reconstructs
    )
    vs_a.start()
    vs_b = env = worker = wt = None
    stop_load = threading.Event()
    loaders: list[threading.Thread] = []
    try:
        deadline = time.time() + 20
        while not master.topo.nodes:
            if time.time() > deadline:
                raise TimeoutError("volume server A never registered")
            time.sleep(0.05)
        # one needle, EC-encoded on A, one data shard quarantined:
        # every read is a verified degraded reconstruction
        a = _rq.get(f"http://localhost:{mport}/dir/assign").json()
        fid = a["fid"]
        vid = FileId.parse(fid).volume_id
        nid, cookie = FileId.parse(fid).needle_id, FileId.parse(fid).cookie
        payload = np.random.default_rng(0x6417).integers(
            0, 256, payload_bytes, dtype=np.uint8
        ).tobytes()
        r = _rq.post(
            f"http://{a['url']}/{fid}", files={"file": ("x.bin", payload)}
        )
        if r.status_code != 201:
            raise RuntimeError(f"upload failed: {r.status_code}")
        env = ShellEnv(f"localhost:{mport}")
        out = run_command(env, f"ec.encode -volumeId {vid} -backend cpu")
        if "generation" not in out:
            raise RuntimeError(f"ec.encode failed: {out}")
        with _grpc.insecure_channel(f"localhost:{vs_a.grpc_port}") as ch:
            _brpc.volume_stub(ch).VolumeEcShardsUnmount(
                _cpb.EcShardsUnmountRequest(volume_id=vid, shard_ids=[0])
            )
        abase = vs_a.service._ec_base(vid, "")
        ev_a = vs_a.store.find_ec_volume(vid)
        migr_sids = sorted(ev_a.shard_fds)
        ground = {
            s: hashlib.sha256(
                open(abase + f".ec{s:02d}", "rb").read()
            ).hexdigest()
            for s in migr_sids
        }
        shard_sz = os.path.getsize(abase + f".ec{migr_sids[0]:02d}")

        # node B: the chip-rich idle destination (telemetry shim — the
        # box has no TPUs, so B REPORTS 8 idle chips; heat and every
        # byte moved stay real)
        vs_b = VolumeServer(
            directories=[os.path.join(gdir, "b")],
            master=f"localhost:{mport}", ip="localhost",
            port=_bench_free_port(), ec_backend="cpu",
            ec_interval_cache_mb=0,
        )
        orig_tele = vs_b._ec_telemetry_json

        def b_tele() -> str:
            blob = json.loads(orig_tele())
            blob["chips"] = {
                f"tpu:{i}": {"load": 0, "breaker": "closed"}
                for i in range(8)
            }
            return json.dumps(blob)

        vs_b._ec_telemetry_json = b_tele
        vs_b.start()
        deadline = time.time() + 20
        while len(master.topo.nodes) < 2:
            if time.time() > deadline:
                raise TimeoutError("volume server B never registered")
            time.sleep(0.05)

        # saturate A's device queue: the competing foreground load the
        # hot volume is stuck behind (the busy-holder half of the
        # skew). Loaders must OUTNUMBER the admission window or a slot
        # is always free and reads never wait.
        queue_a = vs_a.store.ec_scheduler.for_backend(ev_a.backend)
        window = getattr(queue_a, "window", 4) if queue_a else 0

        def loader():
            while not stop_load.is_set():
                with queue_a.admission(
                    "foreground", batch_cost(4, 1 << 20)
                ):
                    time.sleep(0.05)

        if queue_a is not None:
            for _ in range(max(load_threads, window + 2)):
                t = threading.Thread(target=loader, daemon=True)
                t.start()
                loaders.append(t)

        def read_phase(vs) -> tuple[float, bool]:
            okay = True
            t0 = time.perf_counter()
            for _ in range(reads_per_phase):
                n = vs.store.read_needle(vid, nid, cookie)
                okay = okay and (n.data == payload)
            return time.perf_counter() - t0, okay

        # connected worker BEFORE the scan (param validation needs its
        # ec_migrate descriptor; dispatch needs a live stream)
        worker = Worker(master=f"localhost:{mport}", backend="cpu")
        wt = threading.Thread(target=worker.run, daemon=True)
        wt.start()
        wc = master.worker_control
        deadline = time.time() + 20
        while not wc.snapshot()[0]:
            if time.time() > deadline:
                raise TimeoutError("worker never registered")
            time.sleep(0.05)

        def heat_at_master() -> int:
            for n in master.topo.nodes.values():
                if n.port == vs_a.port:
                    vols = n.ec_telemetry.get("ec_volumes", {})
                    return int(vols.get(str(vid), {}).get("read_bytes", 0))
            return 0

        # warmup (compile/caches), then wait for the heat counters to
        # reach the master so the BASELINE sweep records them
        read_phase(vs_a)
        deadline = time.time() + 20
        while heat_at_master() == 0:
            if time.time() > deadline:
                raise TimeoutError("heat never reached the master")
            time.sleep(0.1)
        heat_at_baseline = heat_at_master()
        if wc.scan_for_ec_rebalance(topo=master.topo):
            return {
                "ec_rebalance_error": "baseline sweep dispatched early"
            }

        # BEFORE: measured degraded reads on the saturated holder
        before_s, ok_before = read_phase(vs_a)
        deadline = time.time() + 30
        while heat_at_master() <= heat_at_baseline:
            if time.time() > deadline:
                raise TimeoutError("post-read heat never reached master")
            time.sleep(0.1)
        heat_floor = heat_at_master()

        rec0 = _M.net_bytes_received_total.snapshot()
        tids = wc.scan_for_ec_rebalance(topo=master.topo, min_heat=1 << 20)
        if not tids:
            return {"ec_rebalance_error": "gravity scan planned nothing"}
        deadline = time.time() + 120
        while True:
            _, tasks = wc.snapshot()
            t = next(t for t in tasks if t["task_id"] == tids[0])
            if t["state"] == "done":
                break
            if t["state"] == "failed":
                return {
                    "ec_rebalance_error": f"ec_migrate failed: {t['error']}"
                }
            if time.time() > deadline:
                return {"ec_rebalance_error": "ec_migrate never finished"}
            time.sleep(0.1)
        rec1 = _M.net_bytes_received_total.snapshot()
        wire_native = _net_counter_delta(rec0, rec1, "native")
        wire_python = _net_counter_delta(rec0, rec1, "python")

        # convergence + the exactly-one-mounted-holder invariant
        deadline = time.time() + 20
        while vs_b.store.find_ec_volume(vid) is None or set(
            vs_b.store.find_ec_volume(vid).shard_fds
        ) != set(migr_sids):
            if time.time() > deadline:
                raise TimeoutError("destination never mounted the set")
            time.sleep(0.1)
        one_holder = vs_a.store.find_ec_volume(vid) is None
        bbase = vs_b.service._ec_base(vid, "")
        identical = all(
            hashlib.sha256(
                open(bbase + f".ec{s:02d}", "rb").read()
            ).hexdigest() == ground[s]
            for s in migr_sids
        )

        # AFTER: the same degraded reads, now served by the idle node
        # (one unmeasured warmup read pays B's coeff/locate caches the
        # way A's warmup did)
        vs_b.store.read_needle(vid, nid, cookie)
        after_s, ok_after = read_phase(vs_b)
        identical = identical and ok_before and ok_after

        before_rps = reads_per_phase / max(before_s, 1e-9)
        after_rps = reads_per_phase / max(after_s, 1e-9)
        return {
            "ec_rebalance_before_reads_per_s": round(before_rps, 2),
            "ec_rebalance_after_reads_per_s": round(after_rps, 2),
            "ec_rebalance_speedup": round(
                after_rps / max(before_rps, 1e-9), 2
            ),
            "ec_rebalance_identical": bool(identical),
            "ec_rebalance_exactly_one_holder": bool(one_holder),
            "ec_rebalance_migrated_shards": len(migr_sids),
            "ec_rebalance_wire_native_mb": round(wire_native / 1e6, 2),
            "ec_rebalance_wire_python_mb": round(wire_python / 1e6, 2),
            "ec_rebalance_native_plane": bool(
                native_io.enabled() and wire_native >= len(migr_sids)
                * shard_sz
            ),
            "ec_rebalance_heat_bytes": int(heat_floor),
            "ec_rebalance_payload_kb": payload_bytes >> 10,
        }
    finally:
        stop_load.set()
        for t in loaders:
            t.join(timeout=5)
        for closer in (
            (lambda: worker.stop()) if worker is not None else None,
            (lambda: env.close()) if env is not None else None,
            (lambda: vs_b.stop()) if vs_b is not None else None,
            vs_a.stop,
            master.stop,
        ):
            if closer is None:
                continue
            try:
                closer()
            except Exception:
                pass


def _tenant_storm_bench(
    n_storm_scopes: int = 6,
    threads_per_scope: int = 4,
    victim_batches: int = 60,
    work_s: float = 0.002,
    budget: int = 4,
) -> dict:
    """ISSUE 16 headline: victim-tenant p99 under a tenant storm with
    the residency budget ON vs OFF, in the same run.

    Shape: one physical "chip" (a fake backend whose device time is a
    lock + {work_s} of serialized work — the admission-policy analogue
    of the emulated 8-device placement bench), oversubscribed by
    `n_storm_scopes` independently-created QueueScopes all owned by one
    storm tenant. Each scope carries the full default window, so the
    combined LOGICAL windows (scopes x window) admit far past the
    physical chip. A well-behaved victim tenant issues serial
    foreground batches through its own scope the whole time.

    OFF phase (`residency=False`, the pre-PR 16 behavior): every
    scope's window admits independently — the victim's batch queues
    behind up to scopes*window storm batches at the device. ON phase
    (one shared ResidencyLedger): total in-flight is capped at the
    physical budget and deficit-weighted fairness ranks the
    low-usage victim ahead of the storm, so its p99 is bounded.
    Evidence in the line: victim p99 both ways, the ratio, and the
    residency invariant from the ledger's own high-watermark ground
    truth (max_inflight <= budget on the storm chip)."""
    from seaweedfs_tpu.ec.device_queue import (
        DEFAULT_WINDOW,
        QueueScope,
        ResidencyLedger,
    )

    class _StormChip:
        """Fake pinned backend: all instances share ONE chip label, so
        every scope's queue charges the same physical residency key."""

        chip_label = "storm:0"

    dev_lock = threading.Lock()

    def run_phase(ledger) -> tuple[list[float], int]:
        """One storm+victim pass; returns (victim batch latencies s,
        peak concurrent device occupancy observed by the fake chip)."""
        occ = {"now": 0, "peak": 0}
        occ_lock = threading.Lock()

        def device_work():
            with occ_lock:
                occ["now"] += 1
                occ["peak"] = max(occ["peak"], occ["now"])
            try:
                with dev_lock:
                    time.sleep(work_s)
            finally:
                with occ_lock:
                    occ["now"] -= 1

        residency = ledger if ledger is not None else False
        storm_scopes = [
            QueueScope(
                window=DEFAULT_WINDOW, tenant="storm", residency=residency
            )
            for _ in range(n_storm_scopes)
        ]
        victim_scope = QueueScope(
            window=DEFAULT_WINDOW, tenant="victim", residency=residency
        )
        stop = threading.Event()

        def storm(scope):
            backend = _StormChip()
            q = scope.for_backend(backend)
            s = q.stream("foreground")
            try:
                while not stop.is_set():
                    t, _ = s.dispatch(device_work, 1)
                    s.release(t)
            finally:
                s.close()

        storm_threads = [
            threading.Thread(target=storm, args=(sc,), daemon=True)
            for sc in storm_scopes
            for _ in range(threads_per_scope)
        ]
        for t in storm_threads:
            t.start()
        time.sleep(0.05)  # let the storm saturate before measuring
        lat: list[float] = []
        vq = victim_scope.for_backend(_StormChip())
        vs = vq.stream("foreground")
        try:
            for _ in range(victim_batches):
                t0 = time.perf_counter()
                t, _ = vs.dispatch(device_work, 1)
                vs.release(t)
                lat.append(time.perf_counter() - t0)
        finally:
            vs.close()
            stop.set()
            for t in storm_threads:
                t.join(timeout=10)
        return lat, occ["peak"]

    def p99(xs: list[float]) -> float:
        return sorted(xs)[max(int(len(xs) * 0.99) - 1, 0)]

    lat_off, peak_off = run_phase(None)
    ledger = ResidencyLedger(budget=budget)
    lat_on, peak_on = run_phase(ledger)
    snap = ledger.snapshot()
    chip = snap["chips"].get("storm:0", {})
    # Ground truth for the residency invariant is the LEDGER's own
    # high-watermark, cross-checked against the fake chip's
    # independently-observed peak occupancy.
    invariant_ok = bool(
        chip and chip.get("max_inflight", 0) <= budget and peak_on <= budget
    )
    off_p99, on_p99 = p99(lat_off), p99(lat_on)
    return {
        "tenant_storm_victim_p99_ms_budget_on": round(on_p99 * 1e3, 2),
        "tenant_storm_victim_p99_ms_budget_off": round(off_p99 * 1e3, 2),
        "tenant_storm_victim_p99_off_over_on": round(
            off_p99 / max(on_p99, 1e-9), 2
        ),
        "tenant_storm_residency_invariant_ok": invariant_ok,
        "tenant_storm_peak_inflight_budget_on": int(peak_on),
        "tenant_storm_peak_inflight_budget_off": int(peak_off),
        "tenant_storm_scopes": n_storm_scopes,
        "tenant_storm_budget": budget,
    }


def _pod_encode_bench(reps: int = 3, width: int | None = None) -> dict:
    """Pod-sharded wide-stream encode (ISSUE 15): the explicit
    NamedSharding/pjit lowering over the FULL device mesh vs the
    per-device shard_map lowering, same data, interleaved best-of-N,
    parity verified against the CPU truth both ways. Runs on whatever
    mesh the current platform exposes: the hermetic stage forces the
    8-virtual-device CPU platform; the device-phase variant (gated on
    the probe reporting >= 2 devices) runs on the real pod, where pjit
    is also the lowering that can span multi-process platforms."""
    import jax

    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ops.rs_jax import RSJax
    from seaweedfs_tpu.parallel import MeshRS, make_mesh, pad_cols

    devs = jax.devices()
    if len(devs) < 2:
        return {"skipped": f"single-device platform ({devs[0].platform})"}
    width = width or int(
        os.environ.get("SEAWEED_BENCH_POD_WIDTH_MB", "4")
    ) << 20
    ctx = DEFAULT_EC_CONTEXT
    rng = np.random.default_rng(0x90D)
    data = rng.integers(0, 256, (ctx.data_shards, width), dtype=np.uint8)
    want_crc = zlib.crc32(
        np.ascontiguousarray(CpuBackend(ctx).encode(data)).tobytes()
    )
    rs = RSJax(ctx.data_shards, ctx.parity_shards, impl="xla")
    mesh = make_mesh(len(devs))
    padded, n = pad_cols(data, len(devs))

    prev = os.environ.get("SEAWEED_EC_POD_PJIT")
    variants: dict[str, MeshRS] = {}
    try:
        os.environ["SEAWEED_EC_POD_PJIT"] = "1"
        variants["pjit"] = MeshRS(rs, mesh)
        os.environ["SEAWEED_EC_POD_PJIT"] = "0"
        variants["shard_map"] = MeshRS(rs, mesh)
    finally:
        if prev is None:
            os.environ.pop("SEAWEED_EC_POD_PJIT", None)
        else:
            os.environ["SEAWEED_EC_POD_PJIT"] = prev

    def one(m: MeshRS) -> tuple[float, bool]:
        staged = m.put(padded)
        t0 = time.perf_counter()
        out = np.asarray(m.encode(staged), dtype=np.uint8)[:, :n]
        dt = time.perf_counter() - t0
        return dt, zlib.crc32(np.ascontiguousarray(out).tobytes()) == want_crc

    # warmup compiles both lowerings; timed passes interleave
    ok = all(one(m)[1] for m in variants.values())
    best = {k: float("inf") for k in variants}
    for _ in range(reps):
        for k, m in variants.items():
            dt, good = one(m)
            ok = ok and good
            best[k] = min(best[k], dt)
    gbs = {
        k: (ctx.parity_shards * width) / best[k] / 1e9 for k in best
    }
    return {
        "pod_encode_pjit_gbs": round(gbs["pjit"], 3),
        "pod_encode_shard_map_gbs": round(gbs["shard_map"], 3),
        "pod_encode_pjit_vs_shard_map": round(
            gbs["pjit"] / max(gbs["shard_map"], 1e-9), 2
        ),
        "pod_encode_identical": bool(ok),
        "pod_encode_devices": len(devs),
        "pod_encode_platform": devs[0].platform,
        "pod_encode_width_mb": width >> 20,
    }


def _bench_sign_v4(
    method: str, netloc: str, path: str, access: str, secret: str,
    region: str = "us-east-1",
) -> dict:
    """Header-auth SigV4 signature for the warm bench's client phases
    (UNSIGNED-PAYLOAD, host+date+content-sha signed) — what an SDK
    sends, so the server's s3.auth stage does real verification work.
    Rides the shared signer next to the verifier (s3/auth.sign_v4) so
    canonicalization lives in one place."""
    from seaweedfs_tpu.s3.auth import sign_v4

    return sign_v4(
        method, path,
        access_key=access, secret_key=secret,
        headers={"host": netloc},
        payload_hash="UNSIGNED-PAYLOAD",
        region=region,
    )


# response headers that legitimately differ per request (ids, clocks) —
# everything else must be bit-identical across the fast/off/hit phases
_WARM_VOLATILE_HEADERS = {
    "date", "x-request-id", "x-sw-trace-id", "x-sw-parent-span",
}


def _warm_capture_get(base: str, headers: dict):
    """(status, stable-headers, body) of one GET — the bit-identity
    unit the warm bench compares across fast-paths on/off/hit."""
    import requests as _rq

    r = _rq.get(f"{base}/bench/obj", timeout=60, headers=headers)
    stable = tuple(sorted(
        (k.lower(), v) for k, v in r.headers.items()
        if k.lower() not in _WARM_VOLATILE_HEADERS
    ))
    return r.status_code, stable, r.content


_WARM_STAGES = ("s3.auth", "filer.lookup", "chunk.fetch")


def _warm_stage_ms(snap0: dict, snap1: dict, requests_n: int) -> dict:
    """Per-request mean milliseconds of each gateway stage between two
    sw_ec_stage_seconds snapshots (summed across op/chip labels)."""
    out: dict[str, float] = {}
    for key, (_c, _t, ssum) in snap1.items():
        stage = key[1] if len(key) >= 2 else ""
        if stage not in _WARM_STAGES:
            continue
        prev = snap0.get(key)
        out[stage] = out.get(stage, 0.0) + ssum - (prev[2] if prev else 0.0)
    return {
        k: round(v * 1000.0 / max(requests_n, 1), 3)
        for k, v in out.items()
    }


def _gateway_warm_bench(
    workdir: str,
    clients: int = 16,
    reads_per_client: int = 25,
    obj_bytes: int = 256 << 10,
) -> dict:
    """Warm-path gateway GETs, fast paths ON vs OFF in ONE run
    (ISSUE 13). After PR 12 the residual warm ceiling was the control
    plane: SigV4 auth + filer lookup in Python per request, plus the
    filer->volume chunk fetch re-buffering through `requests`. The
    fast configuration turns on the SigV4 verdict memo, the
    entry-lookup cache, the chunk fetch over the shard net plane, and
    the native body egress; the off configuration disables all four
    (SEAWEED_EC_NATIVE=0, SEAWEED_S3_AUTH_MEMO=0, chunk plane off,
    entry cache capacity 0). The filer CHUNK cache is off in BOTH
    phases so every GET pays the real lookup+fetch path — the line
    measures this PR's stages, not PR 11's hot cache. Requests are
    SigV4-signed so s3.auth does real verification work; every body is
    byte-verified in the client phase AND one (status, headers, body)
    capture per configuration — off, fast-miss, fast-hit — is asserted
    bit-identical in the emitted line. The per-request
    s3.auth/filer.lookup/chunk.fetch stage budget (PR 9 trace stages)
    and the counter evidence (memo/entry-cache hits, chunk bytes on
    the native plane) ride along."""
    import requests as _rq

    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.s3 import S3Server
    from seaweedfs_tpu.s3 import auth as _s3auth
    from seaweedfs_tpu.s3.auth import Identity, IdentityStore
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils import metrics as _M
    from seaweedfs_tpu.utils import trace as _tr

    gdir = os.path.join(workdir, "gateway_warm")
    os.makedirs(gdir, exist_ok=True)
    mport = _bench_free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[os.path.join(gdir, "v")],
        master=f"localhost:{mport}",
        ip="localhost",
        port=_bench_free_port(),
        ec_backend="cpu",
    )
    vs.start()
    filer = srv = None
    _ENV_KEYS = (
        "SEAWEED_EC_NATIVE", "SEAWEED_S3_AUTH_MEMO",
        "SEAWEED_CHUNK_NET_PLANE",
    )
    prev_env = {k: os.environ.get(k) for k in _ENV_KEYS}
    was_armed = _tr.armed
    try:
        deadline = time.time() + 20
        while not master.topo.nodes:
            if time.time() > deadline:
                raise TimeoutError("volume server never registered")
            time.sleep(0.05)
        # chunk cache OFF: every GET pays lookup + volume fetch — the
        # stages this PR targets (the hot-chunk tier is PR 11's win,
        # measured by gateway_degraded_get). SQLITE store, not
        # MemoryStore: the entry cache's claim is "stop hitting
        # store.find", which only means something against a store
        # whose find costs something (a dict-backed MemoryStore would
        # flatter the off phase).
        from seaweedfs_tpu.filer.filer_store import SqliteStore

        filer = Filer(
            SqliteStore(os.path.join(gdir, "filer.db")),
            master=f"localhost:{mport}",
            chunk_size=256 * 1024, chunk_cache_bytes=0,
        )
        idents = IdentityStore()
        idents.add(Identity("bench", "AKIDBENCH", "bench-secret-13"))
        srv = S3Server(
            filer, ip="localhost", port=_bench_free_port(),
            identities=idents,
        )
        srv.start()
        base = f"http://localhost:{srv.port}"
        netloc = f"localhost:{srv.port}"

        def sign(method, path):
            return _bench_sign_v4(
                method, netloc, path, "AKIDBENCH", "bench-secret-13"
            )

        rng = np.random.default_rng(0x3A3A)
        data = rng.integers(0, 256, obj_bytes, dtype=np.uint8).tobytes()
        assert _rq.put(
            f"{base}/bench", headers=sign("PUT", "/bench")
        ).status_code == 200
        assert _rq.put(
            f"{base}/bench/obj", data=data,
            headers=sign("PUT", "/bench/obj"),
        ).status_code == 200
        get_headers = sign("GET", "/bench/obj")
        # warm both byte paths once (page cache + conns)
        for _ in range(2):
            r = _rq.get(f"{base}/bench/obj", timeout=30,
                        headers=get_headers)
            assert r.status_code == 200 and r.content == data
        _tr.configure(enabled=True)  # stage budget needs the recorder
        ecap = filer.entry_cache.capacity

        # ---------------- OFF: every fast path disabled -------------
        os.environ["SEAWEED_EC_NATIVE"] = "0"
        os.environ["SEAWEED_S3_AUTH_MEMO"] = "0"
        os.environ["SEAWEED_CHUNK_NET_PLANE"] = "0"
        filer.entry_cache.capacity = 0
        filer.entry_cache.clear()
        _s3auth.auth_cache_clear()
        cap_off = _warm_capture_get(base, get_headers)
        s0 = _tr._stage_seconds.snapshot()
        python_phase = _gateway_client_phase(
            base, data, clients, reads_per_client, headers=get_headers
        )
        s1 = _tr._stage_seconds.snapshot()
        stage_python = _warm_stage_ms(
            s0, s1, python_phase.get("requests", 0)
        )

        # ---------------- FAST: memo + entry cache + net plane ------
        for k in _ENV_KEYS:
            os.environ.pop(k, None)
        filer.entry_cache.capacity = ecap
        filer.entry_cache.clear()
        _s3auth.auth_cache_clear()
        cap_miss = _warm_capture_get(base, get_headers)  # cold caches
        cap_hit = _warm_capture_get(base, get_headers)   # memo+entry hit
        memo0 = _M.s3_auth_memo_total.snapshot()
        e0 = filer.entry_cache.stats()
        n0 = _M.net_bytes_received_total.snapshot()
        s0 = _tr._stage_seconds.snapshot()
        native_phase = _gateway_client_phase(
            base, data, clients, reads_per_client, headers=get_headers
        )
        s1 = _tr._stage_seconds.snapshot()
        stage_fast = _warm_stage_ms(s0, s1, native_phase.get("requests", 0))
        memo1 = _M.s3_auth_memo_total.snapshot()
        e1 = filer.entry_cache.stats()
        n1 = _M.net_bytes_received_total.snapshot()

        if "error" in native_phase or "error" in python_phase:
            return {
                "gateway_warm_error": (
                    f"fast={native_phase.get('error')} "
                    f"python={python_phase.get('error')}"
                )
            }
        identical = cap_off == cap_miss == cap_hit
        auth_lookup_fast = (
            stage_fast.get("s3.auth", 0.0)
            + stage_fast.get("filer.lookup", 0.0)
        )
        auth_lookup_python = (
            stage_python.get("s3.auth", 0.0)
            + stage_python.get("filer.lookup", 0.0)
        )
        chunk_native = _net_counter_delta(n0, n1, "native")
        return {
            "gateway_warm_get_gets_per_s": native_phase["gets_per_s"],
            "gateway_warm_get_p50_ms": native_phase["p50_ms"],
            "gateway_warm_get_python_gets_per_s": python_phase["gets_per_s"],
            "gateway_warm_get_python_p50_ms": python_phase["p50_ms"],
            "gateway_warm_fast_vs_python": round(
                native_phase["gets_per_s"]
                / max(python_phase["gets_per_s"], 1e-9),
                2,
            ),
            # bit identity across off / fast-miss / fast-hit, headers
            # included (volatile ids/clocks excluded) — IN THE LINE
            "gateway_warm_identical": bool(identical),
            # per-request stage budget, ms (the ISSUE 13 acceptance
            # metric: auth+lookup share drops >=2x fast vs python)
            "gateway_warm_stage_ms_fast": stage_fast,
            "gateway_warm_stage_ms_python": stage_python,
            "gateway_warm_auth_lookup_speedup": round(
                auth_lookup_python / max(auth_lookup_fast, 1e-6), 2
            ),
            # counter evidence that the fast paths actually engaged
            "gateway_warm_auth_memo_hits": int(
                memo1.get(("hit",), 0) - memo0.get(("hit",), 0)
            ),
            "gateway_warm_entry_cache_hits": int(e1["hits"] - e0["hits"]),
            "gateway_warm_entry_cache_loads": int(
                e1["loads"] - e0["loads"]
            ),
            "gateway_warm_chunk_native_mb": round(chunk_native / 1e6, 1),
            "gateway_warm_clients": clients,
            "gateway_warm_object_kb": obj_bytes >> 10,
            "gateway_warm_errors": native_phase["errors"]
            + python_phase["errors"],
        }
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if not was_armed:
            _tr.configure(enabled=False)
        for closer in (
            (lambda: srv.stop()) if srv is not None else None,
            (lambda: filer.close()) if filer is not None else None,
            vs.stop,
            master.stop,
        ):
            if closer is None:
                continue
            try:
                closer()
            except Exception:
                pass


def _canon_needle(raw: bytes) -> bytes:
    """A needle record's bytes with the append timestamp normalized —
    the only field two write transports may legitimately disagree on."""
    from seaweedfs_tpu.storage.needle import Needle

    n = Needle.from_bytes(bytes(raw))
    n.append_at_ns = 1
    return n.to_bytes()


def _write_bit_identity_probe(vols, ops, payload: bytes) -> bool:
    """The SAME fid written over the native write opcode, the HTTP
    multipart POST, and in-process gRPC WriteNeedle must land
    byte-identical records on disk (name/mime defaulting, flags, CRC)."""
    import requests as _rq

    from seaweedfs_tpu.pb import cluster_pb2 as pb
    from seaweedfs_tpu.storage.file_id import FileId
    from seaweedfs_tpu.storage.types import actual_offset

    os.environ["SEAWEED_CHUNK_NET_PLANE_WRITE"] = "1"
    before = sum(v.net_plane.write_requests for v in vols)
    fid = ops.upload(payload, name="ident.bin", mime="application/x-b")
    # 1 on a bare volume, 2 when the assign lands on a replicated one
    # (the fan-out leg also rides the plane)
    if sum(v.net_plane.write_requests for v in vols) < before + 1:
        return False  # the probe write did not ride the plane
    f = FileId.parse(fid)
    vs = next(v for v in vols if v.store.find_volume(f.volume_id))

    def record() -> bytes:
        vol = vs.store.find_volume(f.volume_id)
        nv = vol.needle_map.get(f.needle_id)
        return vol._pread_record(actual_offset(nv.offset), nv.size)

    raw_plane = record()
    os.environ["SEAWEED_CHUNK_NET_PLANE_WRITE"] = "0"
    loc = ops.master.lookup(f.volume_id)[0]
    rr = _rq.post(
        f"http://{loc.url}/{fid}",
        files={"file": ("ident.bin", payload, "application/x-b")},
        timeout=60,
    )
    if rr.status_code != 201:
        return False
    raw_http = record()
    resp = vs.service.WriteNeedle(
        pb.WriteNeedleRequest(
            volume_id=f.volume_id, needle_id=f.needle_id, cookie=f.cookie,
            data=payload, name="ident.bin", mime="application/x-b",
            is_replicate=True,
        ),
        None,
    )
    if resp.error:
        return False
    raw_grpc = record()
    return (
        _canon_needle(raw_plane) == _canon_needle(raw_http)
        == _canon_needle(raw_grpc)
        and ops.read(fid) == payload
    )


def _group_commit_crash_check(workdir: str) -> bool:
    """SIGKILL between the group-commit durability step and the ack:
    every ACKED needle must replay from the on-disk volume (the bench's
    in-process restatement of tests/test_group_commit.py's matrix)."""
    import multiprocessing

    from seaweedfs_tpu import faults
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    d = os.path.join(workdir, "gc_crash")
    os.makedirs(d, exist_ok=True)
    data = b"acked-then-killed-" * 100

    def child(conn):
        os.environ["SEAWEED_VOLUME_GROUP_COMMIT_MS"] = "10"
        v = Volume(d, 1, create=True)
        v.write_needle(Needle(cookie=0x77, needle_id=1, data=data), fsync=True)
        conn.send("acked")
        faults.inject("volume.write.before_ack", faults.hard_exit(137))
        v.write_needle(Needle(cookie=0x78, needle_id=2, data=data), fsync=True)
        os._exit(0)  # pragma: no cover - the fault kills us first

    mp = multiprocessing.get_context("fork")
    parent, cchild = mp.Pipe()
    p = mp.Process(target=child, args=(cchild,))
    p.start()
    p.join(timeout=60)
    if p.is_alive():
        p.kill()
        return False
    if p.exitcode != 137 or not parent.poll() or parent.recv() != "acked":
        return False
    v = Volume(d, 1, create=False)
    try:
        # needle 1 was acked; needle 2 passed its durability step
        # (before_ack fires after it) — both must replay
        return (
            v.read_needle(1).data == data and v.read_needle(2).data == data
        )
    except Exception:
        return False
    finally:
        v.close()


def _mixed_rw_bench(
    workdir: str,
    clients: int = 48,
    ops_per_client: int = 10,
    obj_bytes: int = 64 << 10,
) -> dict:
    """Mixed 70/30 GET/PUT at high client concurrency, write fast
    paths ON vs OFF in ONE run (ISSUE 18). 48 clients on this 2-core
    box is deep oversubscription (the group-commit batching win is in
    full effect) without the 100-thread scheduler floor that flattens
    the fast phase's p99 tail into pure thread-wakeup jitter. Both
    phases run with
    durable writes (SEAWEED_VOLUME_FSYNC=1) and replication 001, so
    every PUT latency IS time-to-replicated-durable; the fast phase
    turns on the native write opcode (client→primary AND the
    primary→replica fan-out leg) and an 8 ms group-commit window,
    the off phase pins PUTs to HTTP multipart with fsync-per-needle —
    the seed write path. Every GET is byte-verified; the write-side
    native-plane engagement rides in the line from
    sw_net_bytes_received_total{plane=native,direction=write}, and the
    three-transport bit-identity probe runs against the same cluster."""
    import threading

    from seaweedfs_tpu.client.operations import Operations
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer
    from seaweedfs_tpu.utils import metrics as _M

    gdir = os.path.join(workdir, "mixed_rw")
    os.makedirs(gdir, exist_ok=True)
    mport = _bench_free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vols = []
    knobs = (
        "SEAWEED_CHUNK_NET_PLANE_WRITE",
        "SEAWEED_VOLUME_FSYNC",
        "SEAWEED_VOLUME_GROUP_COMMIT_MS",
    )
    prev_env = {k: os.environ.get(k) for k in knobs}
    payload = np.random.default_rng(0x18).integers(
        0, 256, obj_bytes, dtype=np.uint8
    ).tobytes()
    try:
        for i in range(2):
            vs = VolumeServer(
                directories=[os.path.join(gdir, f"v{i}")],
                master=f"localhost:{mport}",
                ip="localhost",
                port=_bench_free_port(),
                ec_backend="cpu",
            )
            vs.start()
            vols.append(vs)
        deadline = time.time() + 15
        while len(master.topo.nodes) < 2:
            if time.time() > deadline:
                return {"mixed_rw_error": "volume servers never registered"}
            time.sleep(0.05)

        def phase(fast: bool) -> dict:
            os.environ["SEAWEED_CHUNK_NET_PLANE_WRITE"] = "1" if fast else "0"
            os.environ["SEAWEED_VOLUME_FSYNC"] = "1"
            os.environ["SEAWEED_VOLUME_GROUP_COMMIT_MS"] = (
                "8" if fast else "0"
            )
            ops = Operations(f"localhost:{mport}")
            lock = threading.Lock()
            put_lat: list[float] = []
            get_lat: list[float] = []
            errors = [0]
            barrier = threading.Barrier(clients)

            def client(c: int) -> None:
                fids: list[str] = []
                try:
                    barrier.wait(timeout=60)
                except threading.BrokenBarrierError:
                    pass
                for i in range(ops_per_client):
                    # deterministic 30% writes; the first op seeds the
                    # client's GET target
                    is_put = not fids or (c * 31 + i) % 10 < 3
                    t0 = time.perf_counter()
                    try:
                        if is_put:
                            fids.append(
                                ops.upload(
                                    payload, name="m.bin", replication="001"
                                )
                            )
                            with lock:
                                put_lat.append(time.perf_counter() - t0)
                        else:
                            ok = ops.read(fids[-1]) == payload
                            with lock:
                                if ok:
                                    get_lat.append(time.perf_counter() - t0)
                                else:
                                    errors[0] += 1
                    except Exception:
                        with lock:
                            errors[0] += 1

            threads = [
                threading.Thread(target=client, args=(c,))
                for c in range(clients)
            ]
            t_all = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t_all
            ops.close()
            if not put_lat or not get_lat:
                return {"error": f"no completed ops (errors={errors[0]})"}
            puts = np.array(sorted(put_lat)) * 1e3
            gets = np.array(sorted(get_lat)) * 1e3
            return {
                "write_p50_ms": round(float(np.percentile(puts, 50)), 2),
                "write_p99_ms": round(float(np.percentile(puts, 99)), 2),
                "read_p99_ms": round(float(np.percentile(gets, 99)), 2),
                "puts": len(put_lat),
                "gets": len(get_lat),
                "errors": errors[0],
                "ops_per_s": round(
                    (len(put_lat) + len(get_lat)) / wall, 1
                ),
            }

        python_phase = phase(fast=False)
        n0 = _M.net_bytes_received_total.snapshot()
        fast_phase = phase(fast=True)
        n1 = _M.net_bytes_received_total.snapshot()
        if "error" in python_phase or "error" in fast_phase:
            return {
                "mixed_rw_error": (
                    f"python={python_phase.get('error')} "
                    f"fast={fast_phase.get('error')}"
                )
            }
        write_native = _net_counter_delta(n0, n1, "native", "write")
        ident_ops = Operations(f"localhost:{mport}")
        try:
            identical = _write_bit_identity_probe(vols, ident_ops, payload)
        finally:
            ident_ops.close()
        acked_durable = _group_commit_crash_check(gdir)
        return {
            "mixed_rw_write_p99_ms_fast": fast_phase["write_p99_ms"],
            "mixed_rw_write_p99_ms_python": python_phase["write_p99_ms"],
            "mixed_rw_write_speedup": round(
                python_phase["write_p99_ms"]
                / max(fast_phase["write_p99_ms"], 1e-9),
                2,
            ),
            # durable+replicated ack latency under the fast config
            "mixed_rw_durable_ms": fast_phase["write_p50_ms"],
            "mixed_rw_read_p99_ms_fast": fast_phase["read_p99_ms"],
            "mixed_rw_read_p99_ms_python": python_phase["read_p99_ms"],
            "mixed_rw_ops_per_s_fast": fast_phase["ops_per_s"],
            "mixed_rw_ops_per_s_python": python_phase["ops_per_s"],
            "mixed_rw_write_native_mb": round(write_native / 1e6, 1),
            "mixed_rw_identical": bool(identical),
            "mixed_rw_acked_durable": bool(acked_durable),
            "mixed_rw_clients": clients,
            "mixed_rw_object_kb": obj_bytes >> 10,
            "mixed_rw_errors": fast_phase["errors"]
            + python_phase["errors"],
        }
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for vs in vols:
            try:
                vs.stop()
            except Exception:
                pass
        try:
            master.stop()
        except Exception:
            pass


def _mq_attach_spill(broker, topic: str) -> None:
    """Give every partition log a dict-backed spill store so segments
    seal out of the memory tail like a filer-backed deployment — the
    precondition for the fetch spool's zero-copy sealed-segment path.
    Content-identical to filer spill; only the storage location of the
    sealed bytes differs (the spool re-materializes them on disk)."""
    st = broker.topic("kafka", topic)
    for plog in st.logs.values():
        segs: dict[int, bytes] = {}
        plog._spill = segs.__setitem__
        plog._load = segs.get


def _mq_crash_child(pdir: str, grpc_port: int, kill_window: int) -> None:
    from seaweedfs_tpu import faults
    from seaweedfs_tpu.mq.broker import MqBrokerServer
    from seaweedfs_tpu.mq.kafka.client import KafkaClient
    from seaweedfs_tpu.mq.kafka.records import Record

    os.environ["SEAWEED_MQ_GROUP_COMMIT_MS"] = "10"
    faults.inject(
        "mq.produce.before_flush",
        faults.hard_exit(137),
        when=faults.nth_call(kill_window),
    )
    srv = MqBrokerServer(
        ip="localhost", grpc_port=grpc_port, kafka_port=0, parity_dir=pdir
    )
    srv.start()
    c = KafkaClient("localhost", srv.kafka.port)
    c.create_topic("gc", partitions=1)
    acked = open(os.path.join(pdir, "..", "acked"), "w")
    for i in range(500):
        c.produce(
            "gc", 0,
            [Record(key=b"k%06d" % i, value=b"v%06d-" % i * 16)],
            acks=-1,
        )
        acked.write(f"{i}\n")
        acked.flush()
        os.fsync(acked.fileno())
    os._exit(0)  # pragma: no cover - the armed window kills us first


def _mq_group_commit_crash_check(workdir: str) -> bool:
    """Hard-kill the MQ broker inside a produce group-commit window:
    every Kafka produce acked before the crash must replay from the
    parity streams after restart, dense and byte-exact (the MQ
    restatement of _group_commit_crash_check)."""
    import multiprocessing

    from seaweedfs_tpu.mq.broker import MqBroker
    from seaweedfs_tpu.mq.kafka.gateway import _unpack_null

    d = os.path.join(workdir, "mq_gc_crash")
    pdir = os.path.join(d, "parity")
    os.makedirs(pdir, exist_ok=True)
    prev = os.environ.get("SEAWEED_MQ_GROUP_COMMIT_MS")
    mp = multiprocessing.get_context("fork")
    p = mp.Process(
        target=_mq_crash_child, args=(pdir, _bench_free_port(), 3)
    )
    p.start()
    p.join(timeout=120)
    if prev is None:
        os.environ.pop("SEAWEED_MQ_GROUP_COMMIT_MS", None)
    else:
        os.environ["SEAWEED_MQ_GROUP_COMMIT_MS"] = prev
    if p.is_alive():
        p.kill()
        return False
    if p.exitcode != 137:
        return False
    acked = -1
    acked_path = os.path.join(d, "acked")
    if os.path.exists(acked_path):
        lines = open(acked_path).read().split()
        if lines:
            acked = int(lines[-1])
    br = MqBroker(parity_dir=pdir)
    try:
        recs = br.topic("kafka", "gc").logs[0].read_from(
            0, max_records=10_000
        )
        for n, (off, _ts, k, v) in enumerate(recs):
            if off != n:
                return False  # replay not dense
            if (_unpack_null(k), _unpack_null(v)) != (
                b"k%06d" % n, b"v%06d-" % n * 16
            ):
                return False  # replay not byte-exact
        return len(recs) >= acked + 1  # acked => replayable
    except Exception:
        return False
    finally:
        br.close()


def _mq_fetch_bit_identity_probe(workdir: str) -> tuple[bool, float]:
    """Fetch the same sealed segments over the native (sn_send_file)
    and Python egress planes: the decoded records must be identical.
    Returns (identical, native_mb)."""
    from seaweedfs_tpu.mq.broker import MqBrokerServer
    from seaweedfs_tpu.mq.kafka.client import KafkaClient
    from seaweedfs_tpu.mq.kafka.records import Record
    from seaweedfs_tpu.utils import metrics as _M

    def native_bytes() -> float:
        return dict(_M.mq_fetch_bytes_total.snapshot()).get(
            ("native",), 0
        )

    srv = MqBrokerServer(
        ip="localhost", grpc_port=_bench_free_port(), kafka_port=0,
        segment_records=64,
    )
    srv.start()
    prev = os.environ.get("SEAWEED_EC_NATIVE")
    try:
        c = KafkaClient("localhost", srv.kafka.port)
        c.create_topic("ident", partitions=1)
        _mq_attach_spill(srv.broker, "ident")
        payload = bytes(range(256))
        for i in range(200):
            c.produce("ident", 0, [Record(key=b"k%03d" % i, value=payload)])

        def drain(client):
            out, off = [], 0
            while off < 200:
                _hw, recs = client.fetch(
                    "ident", 0, off, max_wait_ms=0, max_bytes=1 << 22
                )
                if not recs:
                    break
                out.extend((r.offset, r.key, r.value) for r in recs)
                off = out[-1][0] + 1
            return out

        os.environ["SEAWEED_EC_NATIVE"] = "0"
        py_recs = drain(c)
        os.environ["SEAWEED_EC_NATIVE"] = "1"
        n0 = native_bytes()
        c2 = KafkaClient("localhost", srv.kafka.port)
        nat_recs = drain(c2)
        native_mb = (native_bytes() - n0) / 1e6
        c2.close()
        c.close()
        return (
            len(py_recs) == 200 and py_recs == nat_recs,
            round(native_mb, 2),
        )
    finally:
        if prev is None:
            os.environ.pop("SEAWEED_EC_NATIVE", None)
        else:
            os.environ["SEAWEED_EC_NATIVE"] = prev
        srv.stop()


def _mq_sustained_bench(
    workdir: str,
    producers: int = 4,
    consumers: int = 2,
    records_per_producer: int = 400,
    value_bytes: int = 2048,
) -> dict:
    """Sustained Kafka produce/consume at line rate (ISSUE 20): the
    pooled frame server + group commit + zero-copy fetch spool vs the
    naive thread-per-connection/no-group-commit/Python-egress baseline,
    in ONE run. Every record carries its producer-side timestamp, so
    delivery latency is true produce-call-to-consumer-decode; parity
    lag is sampled live during traffic (the durable-parity bound the
    group committer exists to hold). The mid-traffic broker hard-kill +
    dense byte-exact replay assertion rides in the same line."""
    import threading

    from seaweedfs_tpu.mq.broker import MqBrokerServer
    from seaweedfs_tpu.mq.kafka.client import KafkaClient
    from seaweedfs_tpu.mq.kafka.records import Record
    from seaweedfs_tpu.utils import metrics as _M

    gdir = os.path.join(workdir, "mq_sustained")
    os.makedirs(gdir, exist_ok=True)
    knobs = (
        "SEAWEED_MQ_KAFKA_WORKERS",
        "SEAWEED_MQ_GROUP_COMMIT_MS",
        "SEAWEED_EC_NATIVE",
        "SEAWEED_EC_STREAM_BACKEND",
    )
    prev_env = {k: os.environ.get(k) for k in knobs}
    # This stage runs in the bench PARENT, which must stay off jax (the
    # device stage children take the chip in turn): the broker's stream
    # parity is pinned to the CPU, as mixed_rw pins its servers.
    os.environ["SEAWEED_EC_STREAM_BACKEND"] = "cpu"
    pad = b"\x5a" * max(value_bytes - 8, 0)

    def phase(tuned: bool) -> dict:
        os.environ["SEAWEED_MQ_KAFKA_WORKERS"] = "16" if tuned else "0"
        os.environ["SEAWEED_MQ_GROUP_COMMIT_MS"] = "8" if tuned else "0"
        os.environ["SEAWEED_EC_NATIVE"] = "1" if tuned else "0"
        srv = MqBrokerServer(
            ip="localhost",
            grpc_port=_bench_free_port(),
            kafka_port=0,
            segment_records=64,
            parity_dir=os.path.join(
                gdir, "parity_" + ("tuned" if tuned else "naive")
            ),
        )
        srv.start()
        try:
            setup = KafkaClient("localhost", srv.kafka.port)
            setup.create_topic("wire", partitions=producers)
            setup.close()
            _mq_attach_spill(srv.broker, "wire")
            parities = list(
                srv.broker.topic("kafka", "wire").parity.values()
            )
            lock = threading.Lock()
            deliver_s: list[float] = []
            lag_s: list[float] = []
            consumed = [0]  # bytes
            errors = [0]
            prod_done = threading.Event()

            def producer(idx: int) -> None:
                try:
                    c = KafkaClient(
                        "localhost", srv.kafka.port, client_id=f"p{idx}"
                    )
                    for _i in range(records_per_producer):
                        val = struct.pack(">d", time.perf_counter()) + pad
                        c.produce(
                            "wire", idx, [Record(key=b"k", value=val)],
                            acks=-1,
                        )
                    c.close()
                except Exception:
                    with lock:
                        errors[0] += 1

            def consumer(idx: int) -> None:
                try:
                    c = KafkaClient(
                        "localhost", srv.kafka.port, client_id=f"c{idx}"
                    )
                    mine = list(range(idx, producers, consumers))
                    nxt = {p: 0 for p in mine}
                    idle = 0
                    while any(
                        nxt[p] < records_per_producer for p in mine
                    ):
                        progressed = False
                        for p in mine:
                            if nxt[p] >= records_per_producer:
                                continue
                            _hw, recs = c.fetch(
                                "wire", p, nxt[p],
                                max_wait_ms=50, max_bytes=1 << 22,
                            )
                            now = time.perf_counter()
                            fresh = [
                                r for r in recs if r.offset >= nxt[p]
                            ]
                            if not fresh:
                                continue
                            progressed = True
                            nxt[p] = fresh[-1].offset + 1
                            with lock:
                                for r in fresh:
                                    (t0,) = struct.unpack(
                                        ">d", r.value[:8]
                                    )
                                    deliver_s.append(now - t0)
                                    consumed[0] += len(r.value)
                        if progressed:
                            idle = 0
                        elif prod_done.is_set():
                            # a couple of empty passes once producers
                            # are done = genuinely drained (or wedged)
                            idle += 1
                            if idle >= 3:
                                break
                    c.close()
                except Exception:
                    with lock:
                        errors[0] += 1

            def lag_sampler() -> None:
                while not prod_done.is_set():
                    with lock:
                        lag_s.extend(
                            p.parity_lag_s() for p in parities
                        )
                    time.sleep(0.02)

            pthreads = [
                threading.Thread(target=producer, args=(i,))
                for i in range(producers)
            ]
            cthreads = [
                threading.Thread(target=consumer, args=(i,))
                for i in range(consumers)
            ]
            sampler = threading.Thread(target=lag_sampler)
            t0 = time.perf_counter()
            for t in pthreads + cthreads:
                t.start()
            sampler.start()
            for t in pthreads:
                t.join(timeout=300)
            produce_wall = time.perf_counter() - t0
            prod_done.set()
            for t in cthreads:
                t.join(timeout=300)
            wall = time.perf_counter() - t0
            sampler.join(timeout=10)
            total = producers * records_per_producer
            if errors[0] or len(deliver_s) < total:
                return {
                    "error": (
                        f"errors={errors[0]} "
                        f"delivered={len(deliver_s)}/{total}"
                    )
                }
            # cold replay: a catch-up consumer re-reads every
            # partition from offset 0 — sealed segments egress through
            # the fetch spool (zero-copy native plane when enabled),
            # the backfill/replay case the spool exists for
            rc = KafkaClient(
                "localhost", srv.kafka.port, client_id="replay"
            )

            def replay_pass() -> tuple[int, float]:
                t0 = time.perf_counter()
                nbytes = 0
                for p in range(producers):
                    off = 0
                    while off < records_per_producer:
                        _hw, recs = rc.fetch(
                            "wire", p, off,
                            max_wait_ms=0, max_bytes=1 << 22,
                        )
                        fresh = [r for r in recs if r.offset >= off]
                        if not fresh:
                            raise RuntimeError(
                                f"replay wedged at wire[{p}]@{off}"
                            )
                        off = fresh[-1].offset + 1
                        nbytes += sum(len(r.value) for r in fresh)
                return nbytes, time.perf_counter() - t0

            replay_pass()  # cold: populates the spool (builds)
            replay_bytes, replay_wall = replay_pass()  # warm: egress
            rc.close()
            del_ms = np.array(sorted(deliver_s)) * 1e3
            lag_ms = np.array(sorted(lag_s or [0.0])) * 1e3
            pool = srv.kafka.pool_status()
            return {
                "replay_mb_per_s": round(
                    replay_bytes / 1e6 / max(replay_wall, 1e-9), 2
                ),
                "produce_recs_per_s": round(total / produce_wall, 1),
                "consume_mb_per_s": round(
                    consumed[0] / 1e6 / wall, 2
                ),
                "delivery_p50_ms": round(
                    float(np.percentile(del_ms, 50)), 2
                ),
                "delivery_p99_ms": round(
                    float(np.percentile(del_ms, 99)), 2
                ),
                "parity_lag_p99_ms": round(
                    float(np.percentile(lag_ms, 99)), 2
                ),
                "spool_builds": pool["fetch_spool"]["builds"],
                "kind": pool["kind"],
            }
        finally:
            srv.stop()

    try:
        n0 = dict(_M.mq_fetch_bytes_total.snapshot()).get(("native",), 0)
        naive = phase(tuned=False)
        tuned = phase(tuned=True)
        native_mb = (
            dict(_M.mq_fetch_bytes_total.snapshot()).get(("native",), 0)
            - n0
        ) / 1e6
        if "error" in naive or "error" in tuned:
            return {
                "mq_sustained_error": (
                    f"naive={naive.get('error')} "
                    f"tuned={tuned.get('error')}"
                )
            }
        replay_ok = _mq_group_commit_crash_check(gdir)
        return {
            "mq_produce_recs_per_s_tuned": tuned["produce_recs_per_s"],
            "mq_produce_recs_per_s_naive": naive["produce_recs_per_s"],
            "mq_consume_mb_per_s_tuned": tuned["consume_mb_per_s"],
            "mq_consume_mb_per_s_naive": naive["consume_mb_per_s"],
            "mq_delivery_p99_ms_tuned": tuned["delivery_p99_ms"],
            "mq_delivery_p99_ms_naive": naive["delivery_p99_ms"],
            "mq_delivery_speedup": round(
                naive["delivery_p99_ms"]
                / max(tuned["delivery_p99_ms"], 1e-9),
                2,
            ),
            "mq_replay_mb_per_s_tuned": tuned["replay_mb_per_s"],
            "mq_replay_mb_per_s_naive": naive["replay_mb_per_s"],
            # the group committer's whole job: durable-parity lag stays
            # bounded while the tuned phase runs at full tilt
            "mq_parity_lag_p99_ms_tuned": tuned["parity_lag_p99_ms"],
            "mq_parity_lag_p99_ms_naive": naive["parity_lag_p99_ms"],
            "mq_fetch_native_mb": round(native_mb, 1),
            "mq_spool_builds": tuned["spool_builds"],
            "mq_replay_after_kill_identical": bool(replay_ok),
            "mq_producers": producers,
            "mq_consumers": consumers,
            "mq_value_bytes": value_bytes,
        }
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# --------------------------------------------------------------------------
# Device phase: INDEPENDENTLY WATCHDOGGED STAGES, each in its own
# subprocess, each persisting its JSON fragment to disk the moment it
# completes — a later hang can never erase earlier evidence. The
# parent never imports jax: a chip belongs to one process at a time,
# so the stage children take it in turn, and every stage records its
# rc/duration/attempts into the final line's `stages` trail. A device
# stage that does not end on platform == "tpu" fails the bench.
# --------------------------------------------------------------------------

# the stages that must run on the chip
DEVICE_STAGES = (
    "probe", "kernel_small", "pipeline", "kernel_full", "e2e",
    "pod_encode_device",
)

STAGE_TIMEOUTS = {
    "probe": 150.0,
    "kernel_small": 240.0,
    "pipeline": 360.0,
    "kernel_full": 300.0,
    "e2e": 600.0,
    # pod-placement bench: ALWAYS on the emulated 8-device CPU platform
    # (hermetic — no TPU dependence), so one attempt suffices.
    "placement": 300.0,
    # pod-sharded pjit-vs-shard_map encode: hermetic 8-virtual-device
    # variant always; `pod_encode_device` is the SAME stage unforced,
    # gated on the probe reporting a real multi-chip platform.
    "pod_encode": 240.0,
    "pod_encode_device": 240.0,
    # --self-check only: a child that never returns. 20 s = _run_stage's
    # minimum useful budget (smaller gets skipped as budget_exhausted).
    "selfcheck_hang": 20.0,
}
STAGE_ATTEMPTS = {
    "probe": 1, "kernel_small": 1, "pipeline": 1, "kernel_full": 1, "e2e": 1,
    "placement": 1, "pod_encode": 1, "pod_encode_device": 1,
    "selfcheck_hang": 3,
}
STAGE_BACKOFF = 10.0  # seconds, grows linearly per retry


class _SelectedImplFailed(RuntimeError):
    pass


def _stage_probe() -> dict:
    """Cheapest possible liveness check of the device path: jax init,
    device list, one tiny executed op. Lands first so a later hang still
    leaves the platform/device identity + init timing on record."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    devs = jax.devices()
    init_s = time.perf_counter() - t0
    d = devs[0]
    t0 = time.perf_counter()
    val = int(np.asarray(jnp.arange(4096, dtype=jnp.int32).sum()))
    tiny_s = time.perf_counter() - t0
    return {
        "platform": d.platform,
        "kind": str(d.device_kind),
        "n_devices": len(devs),
        "init_s": round(init_s, 2),
        "tiny_op_s": round(tiny_s, 2),
        "tiny_ok": val == 4096 * 4095 // 2,
    }


def _device_kernel(expected: dict, width: int | None = None) -> dict:
    """Timed kernel micro-bench: distinct pre-staged inputs and
    CRC-verified outputs, for EVERY impl; the headline is the impl the
    platform selects (`JaxBackend`'s choice, or SEAWEED_BENCH_IMPL), and
    the stage fails if that one fails.

    The reps run INSIDE a jitted fori_loop whose carried value is a
    checksum of every output — fetching the scalar forces the whole
    chain — and the per-pass time is the slope between a 3-rep and a
    9-rep loop, which cancels the fixed dispatch and fetch latency. The
    loop indexes a different buffer each rep (i % 3), which also
    defeats loop-invariant hoisting."""
    import jax
    import jax.numpy as jnp

    from seaweedfs_tpu.ops.rs_jax import RSJax

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if width is None:
        width = BLOCK if on_tpu else 1 << 20
    if not on_tpu:
        width = min(width, 1 << 20)
    impls = ["pallas", "pallas_aligned", "xla"] if on_tpu else ["xla"]
    selected = os.environ.get("SEAWEED_BENCH_IMPL") or impls[0]
    if selected not in impls:
        impls = [selected]

    # The xla impl materialises 8x f32 bit-planes (~10 GB at full BLOCK):
    # measure it on a slice — throughput, not capacity, is the metric.
    xla_width = min(width, 1 << 23)

    def _cks_np(out: np.ndarray) -> int:
        red = np.bitwise_xor.reduce(out[:, ::65537].astype(np.int32), axis=0)
        return int(red.sum(dtype=np.int32))

    def measure(impl: str) -> dict:
        w = xla_width if impl == "xla" else width
        bufs_np = [_gen(s, w) for s in SEEDS]
        rs = RSJax(K, M, impl=impl)
        db = jax.device_put(jnp.asarray(np.stack(bufs_np)))

        # --- verification: fetch every output in full, CRC vs CPU
        # truth, and derive the checksum the timed loop must carry.
        verified = True
        want_cks = 0
        for i, seed in enumerate(SEEDS):
            out = np.asarray(rs.encode(db[i]), dtype=np.uint8)
            want = expected.get(str(seed), {}).get(str(w))
            if want is None or _crc_rows(out) != want:
                verified = False
            want_cks ^= _cks_np(out)

        def _mkloop(reps):
            @jax.jit
            def loop(d3):
                def body(i, acc):
                    d = jax.lax.dynamic_index_in_dim(
                        d3, i % REPS, keepdims=False
                    )
                    out = rs.encode(d)
                    red = jnp.bitwise_xor.reduce(
                        out[:, ::65537].astype(jnp.int32)
                    )
                    return acc ^ red.sum().astype(jnp.int32)
                return jax.lax.fori_loop(0, reps, body, jnp.int32(0))
            return loop

        # reps=3 and reps=9: each buffer appears an odd number of
        # times in both, so both loops must return want_cks.
        l_lo, l_hi = _mkloop(REPS), _mkloop(3 * REPS)
        got_lo = int(l_lo(db))  # compile + warmup
        got_hi = int(l_hi(db))
        t0 = time.perf_counter()
        got_hi2 = int(l_hi(db))
        dt_hi = time.perf_counter() - t0
        t0 = time.perf_counter()
        got_lo2 = int(l_lo(db))
        dt_lo = time.perf_counter() - t0
        if {got_lo, got_hi, got_hi2, got_lo2} != {want_cks}:
            verified = False
        dt = (dt_hi - dt_lo) / (2 * REPS)
        if dt <= 0:
            raise RuntimeError(
                f"non-positive per-pass slope ({dt_hi:.4f}s@{3*REPS} vs "
                f"{dt_lo:.4f}s@{REPS}): timing unusable"
            )
        gbs = (K * w) / dt / 1e9
        # --- physical consistency: encode must move >= (1 + m/k) bytes of
        # HBM per data byte; a rate implying more than the chip's bandwidth
        # means the measurement (not the chip) is broken.
        suspect = None
        if on_tpu:
            ceiling = _hbm_ceiling(str(dev.device_kind))
            implied_traffic = gbs * (1.0 + M / K)
            if implied_traffic > ceiling:
                suspect = (
                    f"implied HBM traffic {implied_traffic:.0f} GB/s exceeds "
                    f"{dev.device_kind} ceiling ~{ceiling:.0f} GB/s"
                )
        return {
            "kernel_gbs": gbs,
            "kernel_verified": verified,
            "kernel_suspect": suspect,
            "kernel_width": w,
            "dispatch_overhead_s": round(max(dt_lo - REPS * dt, 0.0), 4),
        }

    by_impl: dict[str, dict] = {}
    for impl in impls:
        try:
            by_impl[impl] = measure(impl)
        except Exception as e:  # noqa: BLE001 — reported per impl below
            by_impl[impl] = {"error": repr(e)[:300]}
    head = by_impl[selected]
    if "error" in head:
        raise _SelectedImplFailed(
            f"selected impl {selected!r} failed: {head['error']}; "
            f"all impls: {by_impl}"
        )
    return {
        **head,
        "kernel_impl": selected,
        "kernel_impls": {
            impl: (
                r if "error" in r else {
                    "kernel_gbs": round(r["kernel_gbs"], 3),
                    "kernel_verified": r["kernel_verified"],
                }
            )
            for impl, r in by_impl.items()
        },
        "kind": str(dev.device_kind),
        "platform": dev.platform,
    }


def _stage_pipeline_file(workdir: str, nbytes: int) -> tuple[str, str]:
    """Materialise the pipeline input where reads cost RAM bandwidth,
    not disk: /dev/shm when it has room, else the workdir with an
    explicit warm-read so the page cache holds it. Returns
    (path, staging_kind). Deterministic content (seeded chunks)."""
    import errno

    chunk = np.random.default_rng(0xF00D).integers(
        0, 256, size=64 << 20, dtype=np.uint8
    ).tobytes()

    def _fill(path: str) -> None:
        with open(path, "wb") as f:
            written = 0
            rot = 0
            while written < nbytes:
                piece = chunk[rot:] + chunk[:rot]  # vary content per chunk
                take = min(len(piece), nbytes - written)
                f.write(piece[:take])
                written += take
                rot = (rot + 4096) % len(chunk)

    shm = "/dev/shm"
    path = None
    try:
        st = os.statvfs(shm)
        if st.f_bavail * st.f_frsize < nbytes + (64 << 20):
            raise OSError(errno.ENOSPC, "tmpfs too small")
        fd, path = tempfile.mkstemp(prefix="seaweed_pipe_", dir=shm)
        os.close(fd)
        _fill(path)
        return path, "tmpfs"
    except OSError:
        # tmpfs raced to full mid-write (or is absent): clean up the
        # partial file, degrade to page-cache staging in the workdir
        if path is not None:
            try:
                os.unlink(path)
            except OSError:
                pass
    path = os.path.join(workdir, "pipeline.bin")
    _fill(path)
    with open(path, "rb") as f:  # warm the cache
        while f.read(64 << 20):
            pass
    return path, "pagecache"


def _run_pipeline(backend, path: str, batch: int, reps: int) -> dict:
    """The full device e2e pipeline minus the disk: striped reads from a
    RAM-backed file -> H2D -> encode -> D2H -> per-shard rolling CRC32C
    of ALL 14 shard streams, double-buffered exactly like the production
    encoder (reader thread / dispatch thread / drain+CRC thread over the
    backend's to_device/encode_staged/to_host hooks). The CRCs make the
    D2H real — a broken block_until_ready cannot fake a number because
    every parity byte is fetched and checksummed on the host.
    Returns {gbs, rep_s: [...], shard_crcs: [14 ints]}."""
    import queue as _queue
    import threading as _threading
    from concurrent.futures import ThreadPoolExecutor

    from seaweedfs_tpu.ec.encoder import _pread_padded
    from seaweedfs_tpu.utils import native

    size = os.path.getsize(path)
    block = size // K  # bytes per data-shard row
    fd = os.open(path, os.O_RDONLY)
    times: list[float] = []
    crcs_out: list[int] | None = None
    try:
        for _rep in range(reps):
            crcs = np.zeros(K + M, np.uint32)
            read_q: _queue.Queue = _queue.Queue(maxsize=2)
            out_q: _queue.Queue = _queue.Queue(maxsize=2)
            errors: list[BaseException] = []
            abort = _threading.Event()

            def _put(q, item) -> bool:
                """Abort-aware put: never blocks forever on a full queue
                whose consumer has stopped."""
                while True:
                    try:
                        q.put(item, timeout=0.2)
                        return True
                    except _queue.Full:
                        if abort.is_set():
                            return False

            def reader():
                try:
                    for off in range(0, block, batch):
                        if abort.is_set():
                            return
                        w = min(batch, block - off)
                        buf = np.empty((K, w), np.uint8)
                        for i in range(K):
                            _pread_padded(fd, buf[i], i * block + off)
                        if not _put(read_q, buf):
                            return
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)
                finally:
                    _put(read_q, None)

            def drainer():
                try:
                    with ThreadPoolExecutor(max_workers=K + M) as ex:
                        while True:
                            item = out_q.get()
                            if item is None:
                                return
                            data, handle = item
                            parity = np.ascontiguousarray(
                                backend.to_host(handle), dtype=np.uint8
                            )

                            def crc_row(i):
                                row = data[i] if i < K else parity[i - K]
                                crcs[i] = native.crc32c(row, int(crcs[i]))

                            list(ex.map(crc_row, range(K + M)))
                except BaseException as e:  # noqa: BLE001
                    errors.append(e)
                    while out_q.get() is not None:
                        pass

            rt = _threading.Thread(target=reader, daemon=True)
            st = _threading.Thread(target=drainer, daemon=True)
            t0 = time.perf_counter()
            rt.start()
            st.start()
            try:
                while True:
                    data = read_q.get()
                    if data is None or errors:
                        break
                    out_q.put(
                        (data, backend.encode_staged(backend.to_device(data)))
                    )
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
            finally:
                if errors:
                    abort.set()
                    try:  # unblock a reader stuck on a full queue
                        while True:
                            read_q.get_nowait()
                    except _queue.Empty:
                        pass
                out_q.put(None)
                rt.join(timeout=120)
                st.join(timeout=120)
            dt = time.perf_counter() - t0
            if errors:
                raise errors[0]
            if rt.is_alive() or st.is_alive():
                raise RuntimeError("pipeline thread wedged")
            times.append(dt)
            got = [int(x) for x in crcs]
            if crcs_out is None:
                crcs_out = got
            elif got != crcs_out:
                raise RuntimeError(
                    "pipeline shard CRCs diverged between reps"
                )
    finally:
        os.close(fd)
    return {
        "gbs": size / min(times) / 1e9,
        "rep_s": [round(t, 3) for t in times],
        "shard_crcs": crcs_out,
    }


def _device_pipeline(
    path: str, expected_crcs: list[int], cpu_gbs: float
) -> dict:
    """Device-side pipeline stage: same striped pipeline, JAX backend.
    Bit-exactness gate: the 14 rolling shard CRCs must equal the CPU
    pipeline's. HBM guard: encode moves >= (1+m/k)x the data bytes."""
    import jax

    from seaweedfs_tpu.ec.backend import JaxBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT

    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    batch = BLOCK if on_tpu else 1 << 22
    backend = JaxBackend(DEFAULT_EC_CONTEXT, n_devices=1)
    r = _run_pipeline(backend, path, batch, REPS)
    gbs = r["gbs"]
    implied = gbs * (1.0 + M / K)
    suspect = None
    if on_tpu and implied > (ceiling := _hbm_ceiling(str(dev.device_kind))):
        suspect = (
            f"implied HBM traffic {implied:.0f} GB/s exceeds "
            f"{dev.device_kind} ceiling ~{ceiling:.0f} GB/s"
        )
    return {
        "pipeline_gbs": gbs,
        "pipeline_rep_s": r["rep_s"],
        "pipeline_verified": r["shard_crcs"] == expected_crcs,
        "pipeline_suspect": suspect,
        "pipeline_vs_cpu_pipeline": (
            round(gbs / cpu_gbs, 3) if cpu_gbs else None
        ),
        "pipeline_batch": batch,
        "kind": str(dev.device_kind),
        "platform": dev.platform,
    }


def _device_e2e(base: str, expected_crcs: list[list[int]], dat_size: int) -> dict:
    """Timed disk->shards encode + 2-shard rebuild on the device backend.
    Bit-exactness: the .ecsum CRCs must equal the CPU run's."""
    from seaweedfs_tpu.ec.backend import JaxBackend
    from seaweedfs_tpu.ec.bitrot import BitrotProtection
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
    from seaweedfs_tpu.ec.encoder import ec_encode_volume
    from seaweedfs_tpu.ec.rebuild import rebuild_ec_files

    backend = JaxBackend(DEFAULT_EC_CONTEXT)
    t0 = time.perf_counter()
    ec_encode_volume(base, backend=backend)
    encode_dt = time.perf_counter() - t0
    prot = BitrotProtection.load(base + ".ecsum")
    result = {
        "e2e_gbs": dat_size / encode_dt / 1e9,
        "e2e_verified": prot.shard_crcs == expected_crcs,
    }

    # BASELINE config 2: rebuild 2 missing shards (one data, one parity),
    # staged (async H2D/compute/D2H) AND synchronous-apply, so the line
    # carries the on-device rebuild_staged_vs_sync overlap ratio.
    # rebuild_ec_files verifies regenerated shards against the sidecar
    # and fails closed, so finishing at all means the rebuild is
    # bit-exact; a failure is recorded without discarding the encode.
    try:
        ctx = DEFAULT_EC_CONTEXT

        def timed_rebuild(staged: bool) -> tuple[float, list[int]]:
            for i in (1, K + 1):
                if os.path.exists(base + ctx.to_ext(i)):
                    os.unlink(base + ctx.to_ext(i))
            t0 = time.perf_counter()
            rebuilt = rebuild_ec_files(base, backend=backend, staged=staged)
            return time.perf_counter() - t0, rebuilt

        # Warmup rebuild first (untimed): the first apply pays XLA jit
        # compilation + coefficient bit-expansion; both timed variants
        # hit the same kernel/coeff caches, so the ratio measures
        # OVERLAP, not who compiled. (Both numbers are therefore warm —
        # warmer than pre-PR3 rounds' single cold rebuild.)
        timed_rebuild(staged=True)
        sync_dt, _ = timed_rebuild(staged=False)
        rebuild_dt, rebuilt = timed_rebuild(staged=True)
        result["rebuild_volume_gbs"] = dat_size / rebuild_dt / 1e9
        result["rebuild_sync_volume_gbs"] = dat_size / sync_dt / 1e9
        result["rebuild_staged_vs_sync"] = round(sync_dt / rebuild_dt, 3)
        result["rebuilt_shards"] = rebuilt
    except Exception as e:  # noqa: BLE001 — partial evidence beats none
        result["rebuild_error"] = repr(e)[:500]
    return result


def _stage_child(name: str, workdir: str) -> None:
    """Run one device stage and persist its fragment ATOMICALLY before
    exiting; the parent reads the file, never this process's stdout."""
    forced = os.environ.get("SEAWEED_BENCH_PLATFORM")
    if forced:
        import jax

        jax.config.update("jax_platforms", forced)
    # --trace-out: arm the flight recorder for this stage and dump its
    # span ring as Chrome trace_event JSON (one file per stage — each
    # stage is its own process, so each owns its own ring).
    trace_out = os.environ.get("SEAWEED_BENCH_TRACE_OUT", "")
    if trace_out:
        from seaweedfs_tpu.utils import trace as _tr

        _tr.configure(enabled=True, ring_size=1024)

    with open(os.path.join(workdir, "verify.json")) as f:
        verify = json.load(f)
    try:
        if name in DEVICE_STAGES:
            # opens the device in THIS process and places the compile
            # cache before the stage's first compile
            from seaweedfs_tpu.utils.devices import local_devices

            platform = local_devices().platform
        if name == "selfcheck_hang":
            time.sleep(600)  # deliberately exceed the watchdog
            result = {"error": "hang_did_not_hang"}
        elif name == "placement":
            # ALWAYS the emulated 8-device CPU platform: hermetic (no
            # TPU dependence), and the acceptance metric is defined on
            # exactly this topology.
            from __graft_entry__ import _force_virtual_cpu_mesh

            _force_virtual_cpu_mesh(8)
            result = _placement_bench()
        elif name == "pod_encode":
            # hermetic variant: same emulated 8-device CPU platform as
            # the placement stage — proves the pjit lowering and its
            # bit-identity without any TPU dependence
            from __graft_entry__ import _force_virtual_cpu_mesh

            _force_virtual_cpu_mesh(8)
            result = _pod_encode_bench()
        elif name == "pod_encode_device":
            # the TPU-pod variant: whatever real multi-chip platform
            # the probe found (the parent gates this stage on it)
            result = _pod_encode_bench()
        elif name == "probe":
            result = _stage_probe()
        elif name == "kernel_small":
            result = _device_kernel(verify["kernel_crcs"], width=SMALL_WIDTH)
        elif name == "kernel_full":
            result = _device_kernel(verify["kernel_crcs"], width=BLOCK)
        elif name == "pipeline":
            result = _device_pipeline(
                verify["pipeline_path"],
                verify["pipeline_crcs"],
                verify["pipeline_cpu_gbs"],
            )
        elif name == "e2e":
            result = _device_e2e(
                verify["volume_base"], verify["shard_crcs"], verify["dat_size"]
            )
        else:
            result = {"error": f"unknown stage {name}"}
        if name in DEVICE_STAGES:
            result.setdefault("platform", platform)
    except _SelectedImplFailed as e:
        result = {"error": "kernel_selected_impl_failed", "detail": str(e)[:2000]}
    except Exception as e:  # noqa: BLE001 — the failure IS the evidence
        result = {"error": type(e).__name__, "detail": repr(e)[:2000]}
    if trace_out:
        from seaweedfs_tpu.utils import trace as _tr

        root, ext = os.path.splitext(trace_out)
        tpath = f"{root}.{name}{ext or '.json'}"
        ttmp = tpath + ".tmp"
        try:
            with open(ttmp, "w") as f:
                json.dump(_tr.chrome_trace(), f)
            os.replace(ttmp, tpath)
        except OSError as e:  # a failed dump must not eat the fragment
            result.setdefault("trace_out_error", repr(e))
    tmp = os.path.join(workdir, f".stage_{name}.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(workdir, f"stage_{name}.json"))


def _run_stage(
    name: str,
    workdir: str,
    remaining,
    attempts: int | None = None,
    stop_on_timeout: bool = False,
) -> dict:
    """Run stage `name` in a watchdogged subprocess. Returns the
    child's persisted fragment merged with the parent-side attempt
    trail ({_rc, _s, _attempts}). A stage with attempts left retries a
    fast in-child failure after a backoff.

    `stop_on_timeout` gives up after the FIRST watchdog timeout instead
    of burning every attempt against a device that hangs."""
    import subprocess

    path = os.path.join(workdir, f"stage_{name}.json")
    if attempts is None:
        attempts = int(
            os.environ.get(
                f"SEAWEED_BENCH_{name.upper()}_ATTEMPTS", STAGE_ATTEMPTS[name]
            )
        )
    trail: list[dict] = []
    for attempt in range(attempts):
        budget = remaining()
        timeout = min(STAGE_TIMEOUTS[name], budget)
        if timeout < 20:
            return {"skipped": "budget_exhausted", "_attempts": trail}
        t0 = time.perf_counter()
        rc: int | str
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--stage", name, workdir],
                capture_output=True,
                text=True,
                timeout=timeout,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            )
            rc = out.returncode
            if out.stderr:
                sys.stderr.write(
                    f"bench[{name}#{attempt}] stderr: {out.stderr[-1500:]}\n"
                )
        except subprocess.TimeoutExpired:
            rc = "timeout"
        trail.append({"rc": rc, "s": round(time.perf_counter() - t0, 1)})
        if os.path.exists(path):
            # A persisted fragment beats the watchdog verdict: the child
            # may have finished its work and hung in teardown — valid
            # evidence must not be discarded.
            try:
                with open(path) as f:
                    result = json.load(f)
            except (OSError, json.JSONDecodeError) as e:
                result = {"error": f"fragment_unreadable: {e!r}"}
            if "error" in result and attempt + 1 < attempts and not (
                rc == "timeout" and stop_on_timeout
            ):
                # a fast in-child failure retries like a hang does
                trail[-1]["error"] = str(result["error"])[:200]
                os.unlink(path)
            else:
                result["_attempts"] = trail
                return result
        if rc == "timeout" and stop_on_timeout:
            return {"error": "device_hung", "_attempts": trail}
        if attempt + 1 < attempts:
            backoff = min(STAGE_BACKOFF * (attempt + 1), max(remaining(), 0))
            time.sleep(backoff)
    return {
        "error": "device_hung" if trail and trail[-1]["rc"] == "timeout" else "no_fragment",
        "_attempts": trail,
    }


# --------------------------------------------------------------------------

def _disk_write_gbs(workdir: str, nbytes: int = 256 << 20) -> float:
    """Measured write+fsync ceiling of the bench volume's disk — context
    for the e2e number: once host overhead is gone, e2e is bound by
    min(disk, kernel) and the line should say which."""
    path = os.path.join(workdir, "disk_probe.bin")
    buf = np.random.default_rng(1).integers(0, 256, size=1 << 22, dtype=np.uint8)
    b = buf.tobytes()
    t0 = time.perf_counter()
    with open(path, "wb") as f:
        for _ in range(nbytes // len(b)):
            f.write(b)
        f.flush()
        os.fsync(f.fileno())
    dt = time.perf_counter() - t0
    os.unlink(path)
    return nbytes / dt / 1e9


def _self_check() -> int:
    """Fast regression asserts (no device, no volume fabrication):

    1. A hung stage under `stop_on_timeout` burns exactly ONE watchdog
       attempt, inside a bounded wall time.
    2. The shared device queue is bit-identical to the direct staged
       path, and a colocated recovery stream neither starves nor gets
       starved (loose bounds; the measured bar lives in the bench line).
    """
    failures: list[str] = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        print(f"self-check {name}: {'OK' if ok else 'FAIL ' + detail}")
        if not ok:
            failures.append(name)

    workdir = tempfile.mkdtemp(prefix="seaweed_selfcheck_")
    try:
        with open(os.path.join(workdir, "verify.json"), "w") as f:
            json.dump({}, f)
        t0 = time.perf_counter()
        r = _run_stage(
            "selfcheck_hang", workdir, lambda: 120.0, stop_on_timeout=True
        )
        dt = time.perf_counter() - t0
        check(
            "hang_single_attempt",
            r.get("error") == "device_hung" and len(r["_attempts"]) == 1,
            f"got {r}",
        )
        check("hang_bounded_wall", dt < 2 * STAGE_TIMEOUTS["selfcheck_hang"] + 5,
              f"{dt:.1f}s")

        from seaweedfs_tpu.ec.backend import CpuBackend, _decode_coeffs
        from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT
        from seaweedfs_tpu.ec.device_queue import DeviceQueue
        from seaweedfs_tpu.ec.pipeline import run_staged_apply
        from seaweedfs_tpu.ops import gf256

        ctx = DEFAULT_EC_CONTEXT
        be = CpuBackend(ctx)
        rs = gf256.ReedSolomon(ctx.data_shards, ctx.parity_shards)
        coeffs = _decode_coeffs(
            rs.matrix, ctx.data_shards, (0,), tuple(range(1, 11))
        )
        rng = np.random.default_rng(7)
        total = 4 * 8192 + 99
        data = rng.integers(0, 256, (ctx.data_shards, total), dtype=np.uint8)
        want = be.apply(coeffs, data)
        out = np.zeros((1, total), np.uint8)

        def produce():
            for off in range(0, total, 8192):
                yield off, data[:, off : off + 8192]

        def consume(off, rec):
            out[:, off : off + rec.shape[1]] = rec

        run_staged_apply(
            be, coeffs, produce, consume,
            priority="foreground", device_queue=DeviceQueue(),
        )
        check("queue_bit_identical", bool(np.array_equal(out, want)))

        colo = _colocated_bench(batch=1 << 18, fg_batches=12, reps=2)
        check(
            "colocated_fairness",
            colo["encode_vs_rebuild_colocated"] >= 0.5
            and colo["colocated_recovery_bps"] > 0,
            f"{colo}",
        )

        # ---- residency invariant (ISSUE 16): under an oversubscribed
        # tenant storm the shared ledger's high-watermark never exceeds
        # the physical budget, cross-checked against the fake chip's
        # own peak-occupancy observation --------------------------------
        storm = _tenant_storm_bench(
            n_storm_scopes=3, threads_per_scope=2, victim_batches=20,
            work_s=0.001,
        )
        check(
            "tenant_storm_residency_invariant",
            storm["tenant_storm_residency_invariant_ok"]
            and storm["tenant_storm_peak_inflight_budget_on"]
            <= storm["tenant_storm_budget"],
            f"{storm}",
        )

        # ---- pod placement smoke (no jax: the ChipPool routing core
        # takes any device list + factory) -----------------------------
        from seaweedfs_tpu.ec.chip_pool import ChipPool
        from seaweedfs_tpu.ec.device_queue import batch_cost

        rng = np.random.default_rng(0xA11)
        arrivals = [int(c) for c in rng.integers(1, 1000, 32)]
        # replay the documented policy by hand: least outstanding cost,
        # ties to the lowest index — the pool must match it exactly for
        # a seeded arrival order (routing determinism)
        loads = [0] * 8
        expect = []
        for c in arrivals:
            j = min(range(8), key=lambda x: (loads[x], x))
            expect.append(j)
            loads[j] += c
        pool = ChipPool(range(8), lambda d: f"chip{d}")
        placed = [pool.acquire(c) for c in arrivals]
        check(
            "placement_routing_deterministic",
            [p[0] for p in placed] == expect and pool.loads() == loads,
            f"got={[p[0] for p in placed]} want={expect}",
        )
        for _, _, rel in placed:
            rel()
        check("placement_load_drains", pool.idle() and pool.loads() == [0] * 8)

        # ---- peer-fetch rebuild bit-identity (no servers: injected
        # byte transport) — a shard regenerated from PEER-FETCHED
        # sources must be byte-equal to one regenerated from local
        # sources, and both to the original -------------------------
        from seaweedfs_tpu.ec.bitrot import (
            BitrotProtection,
            ShardChecksumBuilder,
        )
        from seaweedfs_tpu.ec.context import ECContext
        from seaweedfs_tpu.ec.peer_rebuild import rebuild_from_peers
        from seaweedfs_tpu.ec.rebuild import rebuild_ec_files

        pctx = ECContext(4, 2)
        pbe = CpuBackend(pctx)
        prng = np.random.default_rng(0x9EE5)
        pdata = prng.integers(0, 256, (4, 3 * 4096 + 57), dtype=np.uint8)
        pshards = np.concatenate([pdata, pbe.encode(pdata)], axis=0)
        builders = [ShardChecksumBuilder(4096) for _ in range(6)]
        peer_dir = os.path.join(workdir, "peer")
        local_dir = os.path.join(workdir, "local")
        ref_dir = os.path.join(workdir, "ref")
        for d in (peer_dir, local_dir, ref_dir):
            os.makedirs(d)
        for i in range(6):
            b = pshards[i].tobytes()
            builders[i].write(b)
            with open(os.path.join(peer_dir, f"1.ec{i:02d}"), "wb") as f:
                f.write(b)
        prot = BitrotProtection.from_builders(pctx, builders, generation=1)
        # local holds 2 of k=4 sources; shard 5 is the rebuild target
        for d in (local_dir, ref_dir):
            prot.save(os.path.join(d, "1.ecsum"))
            for i in (0, 1):
                with open(os.path.join(d, f"1.ec{i:02d}"), "wb") as f:
                    f.write(pshards[i].tobytes())
        # reference: a LOCAL rebuild with all sources on disk
        for i in (2, 3):
            with open(os.path.join(ref_dir, f"1.ec{i:02d}"), "wb") as f:
                f.write(pshards[i].tobytes())
        rebuild_ec_files(os.path.join(ref_dir, "1"), pctx, backend=pbe)

        def pfetch(peer, sid, off, size):
            with open(os.path.join(peer_dir, f"1.ec{sid:02d}"), "rb") as f:
                f.seek(off)
                return f.read(size)

        rep = rebuild_from_peers(
            os.path.join(local_dir, "1"),
            {2: ["p"], 3: ["p"], 4: ["p"]},
            pfetch,
            ctx=pctx,
            targets=[5],
            backend=pbe,
        )
        peer_bytes = open(os.path.join(local_dir, "1.ec05"), "rb").read()
        ref_bytes = open(os.path.join(ref_dir, "1.ec05"), "rb").read()
        check(
            "peer_fetch_bit_identical",
            rep.rebuilt == [5]
            and peer_bytes == ref_bytes
            and peer_bytes == pshards[5].tobytes(),
            f"rebuilt={rep.rebuilt} equal_ref={peer_bytes == ref_bytes}",
        )

        # ---- leaf-repair bit-identity (no servers): a shard healed by
        # the journal-backed IN-PLACE leaf patch must be byte-equal to
        # one healed by a full rebuild, and both to the original ------
        from seaweedfs_tpu.ec.repair_journal import (
            apply_leaf_repair,
            journal_path,
            leaf_verdict,
            reconstruct_leaves,
        )

        lctx = ECContext(4, 2)
        lbe = CpuBackend(lctx)
        lrng = np.random.default_rng(0x1EAF)
        LEAF, LBLOCK = 1024, 4096
        ldata = lrng.integers(0, 256, (4, 3 * 4096 + 57), dtype=np.uint8)
        lshards = np.concatenate([ldata, lbe.encode(ldata)], axis=0)
        lbuilders = [
            ShardChecksumBuilder(LBLOCK, leaf_size=LEAF) for _ in range(6)
        ]
        repair_dir = os.path.join(workdir, "leafrepair")
        rebuild_dir = os.path.join(workdir, "leafrebuild")
        for d in (repair_dir, rebuild_dir):
            os.makedirs(d)
        for i in range(6):
            b = lshards[i].tobytes()
            lbuilders[i].write(b)
            for d in (repair_dir, rebuild_dir):
                with open(os.path.join(d, f"1.ec{i:02d}"), "wb") as f:
                    f.write(b)
        lprot = BitrotProtection.from_builders(lctx, lbuilders, generation=1)
        for d in (repair_dir, rebuild_dir):
            lprot.save(os.path.join(d, "1.ecsum"))
        # same rot both ways: flip bytes inside leaf 2 of shard 3
        for d in (repair_dir, rebuild_dir):
            with open(os.path.join(d, "1.ec03"), "r+b") as f:
                f.seek(2 * LEAF + 31)
                f.write(b"\xba\xad")
        lbase = os.path.join(repair_dir, "1")
        lpath = lbase + ".ec03"
        lbad = leaf_verdict(lpath, 3, lprot)
        lpatches = reconstruct_leaves(
            lprot, lctx, 3, lbad,
            lambda sid, lo, size: open(
                lbase + f".ec{sid:02d}", "rb"
            ).read()[lo : lo + size],
            [i for i in range(6) if i != 3],
            backend=lbe,
        )
        apply_leaf_repair(lpath, 3, lprot, lpatches)
        # full rebuild path on the twin copy (verify-and-exclude
        # replaces the corrupt shard wholesale)
        rebuild_ec_files(os.path.join(rebuild_dir, "1"), lctx, backend=lbe)
        lrepaired = open(lpath, "rb").read()
        lrebuilt = open(os.path.join(rebuild_dir, "1.ec03"), "rb").read()
        check(
            "leaf_repair_bit_identical",
            lbad == [2]
            and lrepaired == lshards[3].tobytes()
            and lrepaired == lrebuilt
            and not os.path.exists(journal_path(lpath)),
            f"bad={lbad} equal_orig={lrepaired == lshards[3].tobytes()} "
            f"equal_rebuild={lrepaired == lrebuilt}",
        )

        # ---- flight recorder: the DISARMED tracer must never tax the
        # hot path (its per-batch touches are a single is-None check +
        # singleton no-op), and the ARMED tracer must actually record
        # stage-attributed spans ---------------------------------------
        from seaweedfs_tpu.utils import trace as _tr

        noop = _tr.stage(None, "disk_read")
        check(
            "tracer_disarmed_noop_singleton",
            not _tr.armed
            and noop is _tr.stage(None, "h2d_dispatch")
            and _tr.start("ec.encode") is None
            and _tr.current() is None,
        )
        # Measured per-call cost of the disarmed fast path, extrapolated
        # to the pipelined encode's call volume (~8 tracer touches per
        # batch: stage timers in producer/transform/drain/sink plus the
        # queue-put checks): must be <2% of the measured per-batch wall.
        calls = 200_000
        t0 = time.perf_counter()
        for _ in range(calls):
            with _tr.stage(None, "disk_read"):
                pass
        per_call = (time.perf_counter() - t0) / calls
        n_batches = -(-total // 8192)
        t0 = time.perf_counter()
        run_staged_apply(
            be, coeffs, produce, consume,
            priority="foreground", device_queue=DeviceQueue(),
        )
        pipeline_wall = time.perf_counter() - t0
        overhead = 8 * per_call * n_batches / pipeline_wall
        check(
            "tracer_disarmed_overhead_lt_2pct",
            overhead < 0.02,
            f"per_call={per_call * 1e9:.0f}ns batches={n_batches} "
            f"wall={pipeline_wall * 1e3:.1f}ms frac={overhead:.5f}",
        )
        _tr.configure(enabled=True)
        try:
            _tr.reset()
            tsp = _tr.start("ec.encode", name="selfcheck")
            with _tr.activate(tsp):
                run_staged_apply(
                    be, coeffs, produce, consume,
                    priority="foreground", device_queue=DeviceQueue(),
                    span=tsp,
                )
            _tr.finish(tsp)
            docs = _tr.traces()
            doc = docs[-1] if docs else {"stages": {}}
            chrome = _tr.chrome_trace()
            check(
                "tracer_armed_records_stages",
                bool(docs)
                and {"h2d_dispatch", "device_drain"} <= set(doc["stages"])
                and doc.get("overlap_efficiency") is not None
                and any(
                    ev.get("ph") == "X" for ev in chrome["traceEvents"]
                ),
                f"stages={sorted(doc['stages'])}",
            )
        finally:
            _tr.configure(enabled=False)
            _tr.reset()

        # queue-cost accounting: admitted/drained cost sums equal the
        # dispatched work, and the load gauge returns to zero
        q2 = DeviceQueue(window=3)
        costs = {"foreground": [batch_cost(4, w) for w in (64, 4096, 17)],
                 "recovery": [batch_cost(1, w) for w in (4096, 9)]}
        for cls, cs in costs.items():
            s2 = q2.stream(cls)
            try:
                for c in cs:
                    t2, _ = s2.dispatch(lambda: None, c)
                    s2.release(t2)
            finally:
                s2.close()
        st2 = q2.stats()
        check(
            "queue_cost_accounting",
            all(
                st2[cls]["admitted_cost"] == st2[cls]["drained_cost"]
                == sum(cs)
                for cls, cs in costs.items()
            )
            and q2.load() == 0,
            f"{st2}",
        )

        # ---- hot-cache bit-identity (ISSUE 11): the same degraded
        # read with the cache ENABLED vs DISABLED returns identical
        # bytes (and a cache HIT equals the read that populated it) ---
        from seaweedfs_tpu.ec import EcVolume, ec_encode_volume
        from seaweedfs_tpu.storage.needle import Needle
        from seaweedfs_tpu.storage.volume import Volume

        cctx = ECContext(4, 2)
        cdir = os.path.join(workdir, "cachebit")
        os.makedirs(cdir)
        cvol = Volume(cdir, 1)
        crng = np.random.default_rng(0xCACE)
        cpayloads = {}
        for i in range(1, 9):
            dd = crng.integers(0, 256, 20_000, dtype=np.uint8).tobytes()
            cvol.write_needle(
                Needle(cookie=0x100 + i, needle_id=i, data=dd)
            )
            cpayloads[i] = dd
        cvol.close()
        cbase = Volume.base_file_name(cdir, "", 1)
        ec_encode_volume(cbase, cctx, backend=CpuBackend(cctx))
        vol_cached = EcVolume(cdir, 1, backend_name="cpu")
        vol_raw = EcVolume(cdir, 1, backend_name="cpu",
                           interval_cache_bytes=0)
        vol_cached.unmount_shards([0])
        vol_raw.unmount_shards([0])
        cache_ok = True
        for i in range(1, 9):
            a = vol_cached.read_needle(i).data  # populates the cache
            b = vol_cached.read_needle(i).data  # hot-tier hit
            c = vol_raw.read_needle(i).data  # cache-off reconstruction
            if not (a == b == c == cpayloads[i]):
                cache_ok = False
                break
        hc = vol_cached.interval_cache
        check(
            "hot_cache_bit_identical",
            cache_ok and hc is not None and hc.hits > 0 and hc.loads > 0,
            f"ok={cache_ok} stats={hc.stats() if hc else None}",
        )
        vol_cached.close()
        vol_raw.close()

        # ---- network-plane bit identity (ISSUE 12): a shard rebuilt
        # from NATIVE-plane peer fetches (real loopback ShardNetPlane,
        # sendfile egress, recv-into-pooled-buffer ingress with fused
        # copy-in CRC) must be byte-equal to the Python-plane rebuild
        # over the same wire, and both to the original; the sw_net_*
        # counters must attribute bytes to both planes ---------------
        net_stats = _peer_rebuild_bench(workdir, shard_mb=1, reps=1)
        check(
            "net_plane_bit_identical",
            net_stats.get("peer_rebuild_identical") is True,
            f"stats={net_stats}",
        )
        check(
            "net_plane_zero_copy_evidence",
            "peer_rebuild_error" not in net_stats
            and net_stats.get("bytes_copied_per_byte_served_native", 1.0)
            < 0.01
            and net_stats.get("bytes_copied_per_byte_served_python", 0.0)
            >= 1.0,
            f"stats={net_stats}",
        )

        # ---- warm-path fast-path bit identity (ISSUE 13): one run of
        # the warm bench with fast paths ON vs OFF vs HIT — status,
        # stable headers, and body must be byte-equal across all three,
        # and the counter evidence must show the fast paths actually
        # engaged (memo hits, entry-cache hits, chunk bytes native) ---
        warm = _gateway_warm_bench(workdir, clients=2, reads_per_client=4)
        check(
            "warm_path_bit_identical",
            warm.get("gateway_warm_identical") is True
            and warm.get("gateway_warm_errors", 1) == 0,
            f"stats={ {k: v for k, v in warm.items() if 'stage' not in k} }",
        )
        check(
            "warm_path_fast_paths_engaged",
            warm.get("gateway_warm_auth_memo_hits", 0) > 0
            and warm.get("gateway_warm_entry_cache_hits", 0) > 0
            and warm.get("gateway_warm_chunk_native_mb", 0.0) > 0,
            f"memo={warm.get('gateway_warm_auth_memo_hits')} "
            f"entry={warm.get('gateway_warm_entry_cache_hits')} "
            f"native_mb={warm.get('gateway_warm_chunk_native_mb')}",
        )

        # ---- write-path bit identity + acked-durable (ISSUE 18): one
        # small mixed_rw run — the native write opcode, HTTP multipart,
        # and gRPC WriteNeedle land byte-identical records (and the
        # fast phase's writes actually rode the plane); a SIGKILL
        # between the group-commit fsync and the ack must leave every
        # acked needle replayable from disk ---------------------------
        mixed = _mixed_rw_bench(workdir, clients=4, ops_per_client=4)
        check(
            "write_path_bit_identical",
            mixed.get("mixed_rw_identical") is True
            and mixed.get("mixed_rw_errors", 1) == 0
            and mixed.get("mixed_rw_write_native_mb", 0.0) > 0,
            f"stats={mixed}",
        )
        check(
            "group_commit_acked_is_durable",
            _group_commit_crash_check(workdir),
        )

        # ---- streaming-EC bit identity (ISSUE 14): N appends through
        # the online encoder == ONE batch encode over the concat, and
        # the streaming path's p99 time-to-durable-parity beats the
        # naive seal-then-encode baseline in the same run ------------
        stream_stats = _streaming_encode_bench(
            workdir, n_appends=400, append_bytes=4096,
            flush_kib=64, naive_segment_mb=1,
        )
        check(
            "stream_vs_batch_bit_identical",
            stream_stats.get("stream_vs_batch_identical") is True
            and stream_stats.get("streaming_parity_covered") == 400,
            f"stats={stream_stats}",
        )
        check(
            "streaming_parity_beats_seal_then_encode",
            stream_stats.get("time_to_durable_parity_p99_ms", 1e9)
            < stream_stats.get("naive_parity_p99_ms", 0.0),
            f"stream p99={stream_stats.get('time_to_durable_parity_p99_ms')}"
            f" naive p99={stream_stats.get('naive_parity_p99_ms')}",
        )

        # ---- entry-lookup singleflight: concurrent warm misses on ONE
        # entry collapse to ONE store.find --------------------------
        import threading as _th

        from seaweedfs_tpu.filer import Filer as _WFiler
        from seaweedfs_tpu.filer import MemoryStore as _WMemStore

        wf = _WFiler(_WMemStore(), master="localhost:1")
        try:
            wf.write_file("/sf/obj", b"collapse")
            wf.entry_cache.clear()
            finds = [0]
            flock = _th.Lock()
            real_find = wf.store.find

            def counting_find(directory, name):
                with flock:
                    finds[0] += 1
                time.sleep(0.05)  # hold the flight open so misses pile up
                return real_find(directory, name)

            wf.store.find = counting_find
            bodies = []

            def rd():
                bodies.append(wf.find_entry("/sf/obj").to_bytes())

            ts = [_th.Thread(target=rd) for _ in range(8)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wf.store.find = real_find
            check(
                "warm_path_lookup_collapse",
                finds[0] == 1 and len(set(bodies)) == 1 and len(bodies) == 8,
                f"store_finds={finds[0]} distinct={len(set(bodies))}",
            )
        finally:
            wf.close()

        # ---- saturated-gateway 503 is a WELL-FORMED S3 error document
        # (Code=SlowDown + Retry-After): SDK clients must parse and
        # back off, not choke on a bare connection close --------------
        import socket as _socket
        import xml.etree.ElementTree as _ET

        import requests as _rq

        from seaweedfs_tpu.filer import Filer as _Filer
        from seaweedfs_tpu.filer import MemoryStore as _MemStore
        from seaweedfs_tpu.s3 import S3Server as _S3Server

        sat_filer = _Filer(_MemStore(), master="localhost:1")
        sat_srv = _S3Server(
            sat_filer, ip="127.0.0.1", port=_bench_free_port(),
            lifecycle_interval=0, http_workers=1, http_queue=0,
        )
        sat_srv.start()
        held = None
        try:
            held = _socket.create_connection(("127.0.0.1", sat_srv.port))
            time.sleep(0.3)  # let the acceptor admit the held conn
            rr = _rq.get(f"http://127.0.0.1:{sat_srv.port}/", timeout=10)
            doc_ok = False
            try:
                doc = _ET.fromstring(rr.content)
                doc_ok = (
                    doc.tag == "Error"
                    and doc.findtext("Code") == "SlowDown"
                    and bool(doc.findtext("Message"))
                )
            except _ET.ParseError:
                pass
            check(
                "saturation_503_s3_error_doc",
                rr.status_code == 503
                and bool(rr.headers.get("Retry-After"))
                and doc_ok,
                f"code={rr.status_code} "
                f"retry_after={rr.headers.get('Retry-After')} "
                f"body={rr.content[:120]!r}",
            )
        finally:
            if held is not None:
                held.close()
            sat_srv.stop()
            sat_filer.close()

        # ---- data gravity (ISSUE 15): one tiny gravity pass over a
        # real 2-node cluster — migrated shards bit-identical (sidecar-
        # verified copy), exactly ONE mounted holder afterwards, and
        # the before/after reads byte-equal ---------------------------
        reb = _ec_rebalance_bench(
            workdir, payload_bytes=256 << 10, reads_per_phase=2,
            load_threads=2,
        )
        check(
            "migration_bit_identical",
            reb.get("ec_rebalance_identical") is True,
            f"stats={reb}",
        )
        check(
            "migration_exactly_one_holder",
            reb.get("ec_rebalance_exactly_one_holder") is True,
            f"stats={reb}",
        )

        # ---- MQ data plane (ISSUE 20): the zero-copy fetch spool must
        # be invisible on the wire (native plane == Python plane, byte
        # for byte), and a broker hard-killed mid-group-commit-window
        # must replay every acked Kafka produce dense and byte-exact --
        ident, native_mb = _mq_fetch_bit_identity_probe(workdir)
        check(
            "mq_fetch_bit_identical",
            ident,
            f"native_mb={native_mb}",
        )
        check(
            "mq_group_commit_acked_is_durable",
            _mq_group_commit_crash_check(
                os.path.join(workdir, "mq_sc")
            ),
        )
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({"self_check": "pass" if not failures else failures}))
    return 0 if not failures else 1


def main() -> int:
    if "--trace-out" in sys.argv:
        # arm the flight recorder in every stage child (env inherits);
        # each stage dumps <out>.<stage>.json in Chrome trace_event
        # format — load in Perfetto / chrome://tracing
        i = sys.argv.index("--trace-out")
        os.environ["SEAWEED_BENCH_TRACE_OUT"] = os.path.abspath(
            sys.argv[i + 1]
        )
    if "--stage" in sys.argv:
        i = sys.argv.index("--stage")
        _stage_child(sys.argv[i + 1], sys.argv[i + 2])
        return 0
    if "--self-check" in sys.argv:
        sys.exit(_self_check())

    import signal

    from seaweedfs_tpu.ops import gf256

    coeffs = gf256.ReedSolomon(K, M).parity
    threads = os.cpu_count() or 1
    volume_mb = int(os.environ.get("SEAWEED_BENCH_VOLUME_MB", "1024"))

    workdir = tempfile.mkdtemp(prefix="seaweed_bench_")

    # Best-so-far line, kept current as evidence lands: if the driver
    # kills the bench (its timeout, not ours) we still emit one valid
    # JSON line on the way out instead of nothing.
    best: dict = {
        "metric": "ec_encode_e2e_10p4(incomplete)",
        "value": 0.0,
        "vs_baseline": 0.0,
        "unit": "GB/s",
    }
    emitted = False

    def _emit() -> None:
        nonlocal emitted
        if not emitted:
            emitted = True
            print(json.dumps(best))
            sys.stdout.flush()

    def _on_term(signum, frame):  # noqa: ARG001
        best["metric"] += f"(killed_sig{signum})"
        _emit()
        os._exit(0)

    signal.signal(signal.SIGTERM, _on_term)

    stages: dict[str, dict] = {}
    best["stages"] = stages
    try:
        # ---- probe first: without a TPU there is no benchmark, and the
        # host-side phases below take many minutes ------------------------
        with open(os.path.join(workdir, "verify.json"), "w") as f:
            json.dump({}, f)
        probe = _run_stage(
            "probe", workdir,
            lambda: STAGE_TIMEOUTS["probe"] + 10.0,
            stop_on_timeout=True,
        )
        stages["probe"] = probe
        if probe.get("platform") != "tpu":
            reason = str(
                probe.get("error", probe.get("platform", "unknown"))
            )[:120]
            best["metric"] = f"ec_encode_e2e_10p4_no_tpu({reason})"
            return 1

        # ---- CPU truth + baseline ---------------------------------------
        cpu_kernel = _cpu_kernel_gbs(_gen(SEEDS[0], BLOCK), coeffs, threads)
        kernel_crcs = _expected_kernel_crcs(coeffs)
        base = _fabricate_volume(workdir, volume_mb << 20)
        disk_gbs = _disk_write_gbs(workdir)
        # Python-plane reference first (its shards are cleared), then
        # the NATIVE-plane encode — the headline e2e — whose shards and
        # sidecar stay on disk for the recovery benches AND must match
        # the Python run bit-for-bit (shard CRCs + v2 leaf CRCs): the
        # zero-copy plane's identity evidence ships in the line itself.
        from seaweedfs_tpu.ec.bitrot import BitrotProtection as _BP

        cpu_e2e_py, shard_crcs_py, _ = _cpu_e2e(base, force_python=True)
        leaf_crcs_py = _BP.load(base + ".ecsum").shard_leaf_crcs
        _clear_shards(base)
        cpu_e2e, shard_crcs, dat_size = _cpu_e2e(base)
        native_identical = bool(
            shard_crcs == shard_crcs_py
            and _BP.load(base + ".ecsum").shard_leaf_crcs == leaf_crcs_py
        )

        # Recovery-path benches (BASELINE configs 2 and 4) on the CPU
        # backend, against the just-encoded volume; both restore the
        # volume bit-exactly before the device phase clears it.
        rebuild_stats = _cpu_rebuild_bench(base, dat_size)
        degraded_stats = _degraded_read_bench(base)
        # Leaf repair vs full rebuild (ISSUE 8): bytes moved + wall
        # time to fix one rotten 64 KiB leaf both ways, bit-identity
        # asserted; restores the volume before the device phase.
        leaf_repair_stats = _leaf_repair_bench(base)
        # Shared device-queue scheduler: foreground encode vs colocated
        # recovery stream on one queue (PR 4 acceptance metric).
        colocated_stats = _colocated_bench()
        # Gateway serving path (ISSUE 9 / direction 5 seed metric):
        # concurrent S3 GET p50/p99 against a degraded EC volume over a
        # real in-process cluster. Failure is evidence, not fatal.
        try:
            gateway_stats = _gateway_bench(workdir)
        except Exception as e:  # noqa: BLE001
            gateway_stats = {"gateway_error": f"{type(e).__name__}: {e}"}
        # Network byte plane (ISSUE 12): peer-fetch rebuild GB/s over a
        # real loopback ShardNetPlane, native vs Python planes with bit
        # identity asserted, + bytes-copied-per-byte-served per plane.
        try:
            peer_rebuild_stats = _peer_rebuild_bench(workdir)
        except Exception as e:  # noqa: BLE001
            peer_rebuild_stats = {
                "peer_rebuild_error": f"{type(e).__name__}: {e}"
            }
        # Warm gateway GETs with the native body egress on vs off — the
        # PR 11 warm-path GIL ceiling is the target.
        try:
            gateway_warm_stats = _gateway_warm_bench(workdir)
        except Exception as e:  # noqa: BLE001
            gateway_warm_stats = {
                "gateway_warm_error": f"{type(e).__name__}: {e}"
            }
        # Streaming EC (ISSUE 14): time-to-durable-parity under a
        # sustained append load vs the naive seal-then-batch-encode
        # baseline, with stream-vs-batch bit identity in the line.
        try:
            streaming_stats = _streaming_encode_bench(workdir)
        except Exception as e:  # noqa: BLE001
            streaming_stats = {
                "streaming_encode_error": f"{type(e).__name__}: {e}"
            }
        # Data gravity (ISSUE 15): degraded-read throughput before vs
        # after one gravity pass (skewed mini-cluster, real worker-
        # driven ec_migrate), migration bit-identity + native wire
        # bytes in the line.
        try:
            rebalance_stats = _ec_rebalance_bench(workdir)
        except Exception as e:  # noqa: BLE001
            rebalance_stats = {
                "ec_rebalance_error": f"{type(e).__name__}: {e}"
            }
        # Write path at line rate (ISSUE 18): mixed 70/30 GET/PUT,
        # native write plane + group commit vs HTTP + fsync-per-needle
        # in one run, with the three-transport bit-identity probe.
        try:
            mixed_rw_stats = _mixed_rw_bench(workdir)
        except Exception as e:  # noqa: BLE001
            mixed_rw_stats = {
                "mixed_rw_error": f"{type(e).__name__}: {e}"
            }
        # Multi-tenant overload safety (ISSUE 16): victim-tenant p99
        # under a tenant storm with the residency budget on vs off,
        # plus the ledger-ground-truth residency invariant.
        try:
            tenant_storm_stats = _tenant_storm_bench()
        except Exception as e:  # noqa: BLE001
            tenant_storm_stats = {
                "tenant_storm_error": f"{type(e).__name__}: {e}"
            }
        # Streaming at line rate (ISSUE 20): sustained Kafka
        # produce/consume, pooled gateway + group commit + zero-copy
        # fetch vs the naive baseline in one run, with the mid-traffic
        # hard-kill replay assertion.
        try:
            mq_sustained_stats = _mq_sustained_bench(workdir)
        except Exception as e:  # noqa: BLE001
            mq_sustained_stats = {
                "mq_sustained_error": f"{type(e).__name__}: {e}"
            }

        _clear_shards(base)  # device phase re-encodes the same volume

        # Disk-independent pipeline: CPU truth run (same striped
        # read->encode->CRC pipeline the device stage executes) is both
        # the verification oracle and the measured same-pipeline CPU
        # baseline. The device e2e above is ~300x disk-bound on this
        # host (BENCH_r04), so this is the number that can actually show
        # a compute win.
        from seaweedfs_tpu.ec.backend import CpuBackend
        from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT

        pipe_mb = int(os.environ.get("SEAWEED_BENCH_PIPELINE_MB", "1024"))
        pipe_path, pipe_staging = _stage_pipeline_file(workdir, pipe_mb << 20)
        cpu_pipe = _run_pipeline(
            CpuBackend(DEFAULT_EC_CONTEXT), pipe_path, BLOCK, REPS
        )

        with open(os.path.join(workdir, "verify.json"), "w") as f:
            json.dump(
                {
                    "kernel_crcs": kernel_crcs,
                    "volume_base": base,
                    "shard_crcs": shard_crcs,
                    "dat_size": dat_size,
                    "pipeline_path": pipe_path,
                    "pipeline_crcs": cpu_pipe["shard_crcs"],
                    "pipeline_cpu_gbs": cpu_pipe["gbs"],
                },
                f,
            )

        common = {
            "unit": "GB/s",
            "threads": threads,
            "volume_gib": round(dat_size / (1 << 30), 3),
            "cpu_e2e_gbs": round(cpu_e2e, 3),
            # native data plane vs pure-Python source/sink, same volume,
            # bit-identity asserted (ISSUE 10 acceptance evidence)
            "cpu_e2e_python_gbs": round(cpu_e2e_py, 3),
            "e2e_native_vs_python": round(cpu_e2e / max(cpu_e2e_py, 1e-9), 3),
            "e2e_native_identical": native_identical,
            "cpu_kernel_gbs": round(cpu_kernel, 3),
            # Honest derating context (north-star baseline is a 16-core
            # host; this one has `threads`): linear-scaling estimate.
            "cpu_kernel_16core_est_gbs": round(cpu_kernel / threads * 16, 3),
            "disk_write_gbs": round(disk_gbs, 3),
            "cpu_pipeline_gbs": round(cpu_pipe["gbs"], 3),
            "cpu_pipeline_16core_est_gbs": round(
                cpu_pipe["gbs"] / threads * 16, 3
            ),
            "pipeline_staging": pipe_staging,
            "pipeline_gib": round((pipe_mb << 20) / (1 << 30), 3),
            **rebuild_stats,
            **degraded_stats,
            **leaf_repair_stats,
            **colocated_stats,
            **gateway_stats,
            **peer_rebuild_stats,
            **gateway_warm_stats,
            **streaming_stats,
            **rebalance_stats,
            **mixed_rw_stats,
            **tenant_storm_stats,
            **mq_sustained_stats,
        }
        best.update(
            {"metric": "ec_encode_e2e_10p4(device_pending)", **common}
        )

        # ---- device stages ----------------------------------------------
        try:
            budget = float(os.environ.get("SEAWEED_BENCH_DEVICE_TIMEOUT", "1200"))
        except ValueError:
            budget = 1200.0

        # Pod-placement bench: always the emulated 8-device CPU
        # platform inside the stage child — hermetic, so it spends
        # none of the device budget (the device deadline starts AFTER
        # it).
        placement_stage = _run_stage(
            "placement", workdir,
            lambda: STAGE_TIMEOUTS["placement"] + 10.0,
        )
        stages["placement"] = placement_stage
        if "multi_stream_placement" in placement_stage:
            for k in (
                "multi_stream_placement", "placed_agg_gbs", "mesh_agg_gbs",
                "placement_verified", "placement_streams", "placement_chips",
            ):
                best[k] = placement_stage[k]

        # Pod-sharded encode, hermetic variant (same forced 8-device
        # CPU platform as the placement stage, so it spends no device
        # budget either): pjit-vs-shard_map with bit-identity — the
        # cross-backend half of the ISSUE 15 acceptance. The real-pod
        # variant runs in the device phase below, gated on the probe.
        pod_stage = _run_stage(
            "pod_encode", workdir,
            lambda: STAGE_TIMEOUTS["pod_encode"] + 10.0,
        )
        stages["pod_encode"] = pod_stage
        for k, v in pod_stage.items():
            if k.startswith("pod_encode_"):
                best[k] = v

        deadline = time.monotonic() + budget
        remaining = lambda: deadline - time.monotonic()  # noqa: E731

        kernel = None
        pipeline: dict = {"skipped": "kernel_small_failed"}
        ks = _run_stage("kernel_small", workdir, remaining)
        stages["kernel_small"] = ks
        if "kernel_gbs" in ks:
            kernel = ks
            # pipeline lands BEFORE kernel_full/e2e: it is the artifact
            # the round is judged on, so it gets budget priority
            pipeline = _run_stage("pipeline", workdir, remaining)
            stages["pipeline"] = pipeline
            kf = _run_stage("kernel_full", workdir, remaining)
            stages["kernel_full"] = kf
            if "kernel_gbs" in kf:
                kernel = kf
        e2e = _run_stage("e2e", workdir, remaining)
        stages["e2e"] = e2e
        # TPU-pod variant of the pod-sharded encode: gated on the
        # probe reporting a multi-device platform (the hermetic
        # 8-virtual-CPU variant above always ran)
        if int(probe.get("n_devices", 1)) >= 2:
            podd = _run_stage("pod_encode_device", workdir, remaining)
            stages["pod_encode_device"] = podd
            for k, v in podd.items():
                if k.startswith("pod_encode_"):
                    best[f"device_{k}"] = v

        # ---- metric selection (best verified evidence wins) --------------
        kind = probe.get("kind", "?")
        if kernel is not None:
            best.update(
                {
                    "kernel_gbs": round(kernel.get("kernel_gbs", 0.0), 3),
                    "kernel_impl": kernel.get("kernel_impl"),
                    "kernel_verified": kernel.get("kernel_verified"),
                    "kernel_suspect": kernel.get("kernel_suspect"),
                    "kernel_width": kernel.get("kernel_width"),
                    "kernel_vs_cpu": round(
                        kernel.get("kernel_gbs", 0.0) / cpu_kernel, 3
                    ),
                    "kernel_vs_16core_est": round(
                        kernel.get("kernel_gbs", 0.0)
                        / (cpu_kernel / threads * 16),
                        3,
                    ),
                }
            )

        if e2e.get("e2e_gbs") is not None:
            best.update(
                {
                    "e2e_gbs": round(e2e["e2e_gbs"], 3),
                    "e2e_verified": e2e.get("e2e_verified", False),
                    "e2e_vs_cpu": round(e2e["e2e_gbs"] / cpu_e2e, 3),
                    "rebuild_volume_gbs": round(
                        e2e.get("rebuild_volume_gbs", 0.0), 3
                    ),
                    # on-device overlap win (CPU-host parity ratio lives
                    # in the top-level rebuild_staged_vs_sync key)
                    "rebuild_staged_vs_sync_device": e2e.get(
                        "rebuild_staged_vs_sync"
                    ),
                    "rebuild_error": e2e.get("rebuild_error"),
                }
            )
        if pipeline.get("pipeline_gbs") is not None:
            best.update(
                {
                    "pipeline_gbs": round(pipeline["pipeline_gbs"], 3),
                    "pipeline_verified": pipeline.get("pipeline_verified"),
                    "pipeline_suspect": pipeline.get("pipeline_suspect"),
                    "pipeline_rep_s": pipeline.get("pipeline_rep_s"),
                    "pipeline_vs_16core_est": round(
                        pipeline["pipeline_gbs"]
                        / (cpu_pipe["gbs"] / threads * 16),
                        3,
                    ),
                }
            )

        # ---- headline: ec_encode_e2e (ROADMAP direction 1) ---------------
        # End-to-end disk->shards encode ON THE CHIP is the metric; the
        # kernel-only and disk-independent pipeline figures are context
        # sub-fields (kernel_gbs / pipeline_gbs above). There is no CPU
        # headline: a device stage that did not end on the TPU, or an
        # encode whose bytes differ, fails the bench.
        failed = {
            name: str(
                st.get("error", st.get("skipped", st.get("platform")))
            )[:120]
            for name, st in stages.items()
            if name in DEVICE_STAGES and st.get("platform") != "tpu"
        }
        if not native_identical:
            failed["cpu_native_plane"] = "differs from the Python plane"
        if not failed and not e2e.get("e2e_verified", False):
            failed["e2e"] = "shard CRCs differ from the CPU run"
        if failed:
            best.update(
                {
                    "metric": f"ec_encode_e2e_10p4_FAILED[{kind}]({failed})",
                    "value": 0.0,
                    "vs_baseline": 0.0,
                }
            )
            return 1
        impl = (kernel or {}).get("kernel_impl")
        best.update(
            {
                "metric": (
                    f"ec_encode_e2e_10p4[{kind}/{impl}"
                    f" vs {threads}-thread avx2 cpu, bit-exact]"
                ),
                "value": round(e2e["e2e_gbs"], 3),
                "vs_baseline": round(e2e["e2e_gbs"] / cpu_e2e, 3),
            }
        )
        return 0
    finally:
        _emit()
        shutil.rmtree(workdir, ignore_errors=True)
        try:  # the pipeline file may live in /dev/shm, outside workdir
            if "pipe_path" in locals() and os.path.exists(pipe_path):
                os.unlink(pipe_path)
        except OSError:
            pass


if __name__ == "__main__":
    sys.exit(main())
