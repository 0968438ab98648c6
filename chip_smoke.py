"""Smoke run of the erasure-coding main path on the TPU, in one process.

    python chip_smoke.py [--seed N]        one chip (what the driver runs)
    python chip_smoke.py --chips 4         the cross-chip paths only

The process asks JAX for its devices first and exits non-zero unless
they are TPUs. With one chip it then brings up a master and a volume
server (`-ec.backend tpu`) in this process and drives them the way a
user does — HTTP `/dir/assign`, HTTP POST/GET on the volume server, the
shell's `ec.encode` / `ec.rebuild` / `ec.scrub` — over one 1 GiB 10+4
volume (BASELINE.json configs 1, 2 and 4), checking every result
against the `CpuBackend` on the same bytes. Each phase prints one JSON
line; rates on those lines are health signals, not metrics. The last
line is the result the driver reads. A failed phase raises: nothing is
caught and carried past.
"""

from __future__ import annotations

import argparse
import contextlib
import filecmp
import json
import os
import shutil
import socket
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MIB = 1 << 20
GIB = 1 << 30
BODY_BYTES = MIB  # the bulk of the load; a few hundred 1-64 KiB beside it
LOST_SHARDS = (3, 11)  # one data shard, one parity shard

# (label, data shards, parity shards, batch width, "encode" | "rebuild"):
# the batch shapes a 1 GiB volume cannot reach. (10, 16 MiB) is
# DEFAULT_BATCH, what a >= 10 GiB volume dispatches; 4+2 at 256 KiB is
# the stream-parity flush.
STAGED_SHAPES = [
    ("10+4 encode @1MiB", 10, 4, MIB, "encode"),
    ("10+4 encode @16MiB", 10, 4, 16 * MIB, "encode"),
    ("10+4 rebuild2 @16MiB", 10, 4, 16 * MIB, "rebuild"),
    ("4+2 encode @256KiB", 4, 2, 256 << 10, "encode"),
]
IMPLS = ("pallas", "xla")


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


# ------------------------------------------------------------- metering


class CompileMeter:
    """Counts XLA compilations and their seconds (jax.monitoring)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.COMPILE:
            with self._lock:
                self.compiles += 1
                self.seconds += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == self.CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        with self._lock:
            return self.compiles, self.seconds, self.cache_hits


def peak_bytes_in_use() -> list[int | None]:
    import jax

    out = []
    for d in jax.devices():
        stats = d.memory_stats()
        out.append(stats.get("peak_bytes_in_use") if stats else None)
    return out


@contextlib.contextmanager
def phase(name: str, meter: CompileMeter):
    """Time one phase and print its line when it PASSES; an exception
    leaves through here untouched."""
    facts: dict = {}
    t0 = time.perf_counter()
    c0, s0, h0 = meter.snapshot()
    yield facts
    c1, s1, h1 = meter.snapshot()
    emit(
        {
            "phase": name,
            "seconds": round(time.perf_counter() - t0, 3),
            **facts,
            "compiles": c1 - c0,
            "compile_seconds": round(s1 - s0, 3),
            "compile_cache_hits": h1 - h0,
            "peak_bytes_in_use": peak_bytes_in_use(),
        }
    )


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -------------------------------------------------------------- helpers


def free_port() -> int:
    """Ephemeral port whose +10000 gRPC shadow is free too."""
    while True:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            p = s.getsockname()[1]
        if p + 10000 > 65535:
            continue
        with socket.socket() as s:
            try:
                s.bind(("localhost", p + 10000))
            except OSError:
                continue
        return p


def disk_write_mbs(directory: str, nbytes: int) -> float:
    """Measured sequential write rate of the data directory."""
    path = os.path.join(directory, ".write_probe")
    chunk = np.random.default_rng(0).bytes(8 * MIB)
    t0 = time.perf_counter()
    with open(path, "wb", buffering=0) as f:
        for _ in range(max(nbytes // len(chunk), 1)):
            f.write(chunk)
        os.fsync(f.fileno())
    dt = time.perf_counter() - t0
    written = os.path.getsize(path)
    os.unlink(path)
    return written / dt / 1e6


def body_of(seed: int, index: int, size: int) -> bytes:
    return np.random.default_rng([seed, index]).bytes(size)


def needle_plan(seed: int, volume_bytes: int) -> list[int]:
    """Body sizes: 1 MiB bodies up to `volume_bytes`, with a few hundred
    of 1-64 KiB (scaled with the volume) mixed in at seeded places."""
    rng = np.random.default_rng([seed, 0xB0D1E5])
    n_small = max(8, 300 * volume_bytes // GIB)
    sizes = [BODY_BYTES] * max(volume_bytes // BODY_BYTES, 1)
    sizes += [int(s) for s in rng.integers(1 << 10, 64 << 10, n_small)]
    rng.shuffle(sizes)
    return sizes


def span_events(doc: dict, name: str):
    for ev in doc.get("events", ()):
        if ev.get("name") == name:
            yield ev
    for child in doc.get("children", ()):
        yield from span_events(child, name)


def same_encoding(base: str, ref_base: str, ctx, shard_ids=None) -> None:
    """Shard files byte-identical and .ecsum block AND leaf CRCs equal.
    (The sidecar file itself carries a uuid and the encode timestamp.)"""
    from seaweedfs_tpu.ec.bitrot import BitrotProtection

    for i in range(ctx.total) if shard_ids is None else shard_ids:
        check(
            filecmp.cmp(
                base + ctx.to_ext(i), ref_base + ctx.to_ext(i), shallow=False
            ),
            f"shard {i} of {base} differs from the CpuBackend reference",
        )
    got = BitrotProtection.load(base + ".ecsum")
    want = BitrotProtection.load(ref_base + ".ecsum")
    for field in (
        "block_size", "shard_sizes", "shard_crcs", "leaf_size",
        "shard_leaf_crcs",
    ):
        check(
            getattr(got, field) == getattr(want, field),
            f".ecsum {field} of {base} differs from the CpuBackend reference",
        )
    check(bool(got.shard_leaf_crcs), ".ecsum carries no leaf CRCs")


# ------------------------------------------------------ one-chip phases


class Cluster:
    """In-process master + one volume server on ephemeral ports."""

    def __init__(self, workdir: str):
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        from seaweedfs_tpu.shell.commands import ShellEnv

        self.data_dir = os.path.join(workdir, "data")
        self.ref_dir = os.path.join(workdir, "ref")
        os.makedirs(self.data_dir)
        os.makedirs(self.ref_dir)
        mport = free_port()
        self.master = MasterServer(ip="localhost", port=mport)
        self.master.start()
        self.vs = VolumeServer(
            directories=[self.data_dir],
            master=f"localhost:{mport}",
            ip="localhost",
            port=free_port(),
            ec_backend="tpu",
            ec_trace=True,
        )
        self.vs.start()
        deadline = time.time() + 30
        while not self.master.topo.nodes:
            check(time.time() < deadline, "volume server did not register")
            time.sleep(0.05)
        self.master_url = f"http://localhost:{mport}"
        self.volume_url = f"http://localhost:{self.vs.port}"
        self.env = ShellEnv(f"localhost:{mport}")
        self.vid = 0
        self.fids: list[str] = []
        self.sizes: list[int] = []

    def shell(self, line: str) -> str:
        from seaweedfs_tpu.shell.commands import run_command

        out = run_command(self.env, line)
        check("error" not in out.lower(), f"`{line}` -> {out}")
        return out

    def stop(self) -> None:
        self.env.close()
        self.vs.stop()
        self.master.stop()


def phase_load(c: Cluster, seed: int, volume_bytes: int, facts: dict) -> None:
    """POST needles over HTTP until the volume holds `volume_bytes`,
    then GET a seeded sample back: an acknowledged write is read back."""
    import requests

    from seaweedfs_tpu.storage.file_id import FileId

    facts["disk_write_mb_s"] = round(
        disk_write_mbs(c.data_dir, min(volume_bytes // 4, 256 * MIB)), 1
    )
    r = requests.get(c.master_url + "/dir/assign", timeout=30)
    check(r.status_code == 200, f"/dir/assign -> {r.status_code} {r.text}")
    first = FileId.parse(r.json()["fid"])
    # The master spreads assigns over the volumes it grows; the volume
    # server takes a write for any fid of a volume it holds, so one
    # volume fills by keeping the first assign's volume id and cookie.
    c.vid = first.volume_id
    c.sizes = needle_plan(seed, volume_bytes)
    c.fids = [
        str(FileId(first.volume_id, first.needle_id + i, first.cookie))
        for i in range(len(c.sizes))
    ]
    local = threading.local()

    def post(i: int) -> None:
        if not hasattr(local, "http"):
            local.http = requests.Session()
        body = body_of(seed, i, c.sizes[i])
        r = local.http.post(
            f"{c.volume_url}/{c.fids[i]}",
            files={"file": (f"n{i}", body, "application/octet-stream")},
            timeout=120,
        )
        check(r.status_code == 201, f"POST {c.fids[i]} -> {r.status_code}")
        # stored size counts the name and mime beside the body
        check(r.json()["size"] >= len(body), f"POST {c.fids[i]} short")

    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=8) as ex:
        list(ex.map(post, range(len(c.sizes))))
    dt = time.perf_counter() - t0
    total = sum(c.sizes)
    sample = np.random.default_rng([seed, 0x5A]).choice(
        len(c.sizes), size=min(64, len(c.sizes)), replace=False
    )
    read_back(c, seed, [int(i) for i in sample])
    facts.update(
        volume=c.vid, needles=len(c.sizes), bytes=total,
        post_mb_s=round(total / dt / 1e6, 1), read_back=len(sample),
    )


def read_back(c: Cluster, seed: int, indices: list[int]) -> int:
    import requests

    got = 0
    with requests.Session() as http:
        for i in indices:
            r = http.get(f"{c.volume_url}/{c.fids[i]}", timeout=120)
            check(r.status_code == 200, f"GET {c.fids[i]} -> {r.status_code}")
            check(
                r.content == body_of(seed, i, c.sizes[i]),
                f"GET {c.fids[i]}: bytes differ from what was written",
            )
            got += len(r.content)
    return got


def phase_encode(c: Cluster, facts: dict) -> None:
    """Shell `ec.encode` on the server's backend vs a CpuBackend encode
    of the same .dat/.idx in a side directory."""
    from seaweedfs_tpu.ec.backend import CpuBackend, JaxBackend, get_backend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as ctx
    from seaweedfs_tpu.ec.encoder import ec_encode_volume
    from seaweedfs_tpu.utils import trace

    base = os.path.join(c.data_dir, str(c.vid))
    ref_base = os.path.join(c.ref_dir, str(c.vid))
    # ec.encode drops the source volume: keep its inodes for the reference
    for ext in (".dat", ".idx"):
        os.link(base + ext, ref_base + ext)
    t0 = time.perf_counter()
    facts["shell"] = c.shell(f"ec.encode -volumeId {c.vid}")
    dt = time.perf_counter() - t0
    dat_bytes = os.path.getsize(ref_base + ".dat")
    backend = get_backend(c.vs.store.ec_backend, ctx.data_shards, ctx.parity_shards)
    check(isinstance(backend, JaxBackend), f"server encodes on {backend!r}")
    root = trace.traces(op="rpc.ec_shards_generate")[-1]
    t0 = time.perf_counter()
    ec_encode_volume(ref_base, ctx, CpuBackend(ctx))
    facts.update(
        bytes=dat_bytes, impl=backend._rs.impl,
        encode_mb_s=round(dat_bytes / dt / 1e6, 1),
        stage_seconds={
            stage: round(s, 3)
            for stage, s in sorted(trace._tree_stage_totals(root).items())
        },
        overlap_efficiency=trace.overlap_efficiency(root),
        cpu_reference_seconds=round(time.perf_counter() - t0, 3),
    )
    same_encoding(base, ref_base, ctx)
    facts["identical_to_cpu"] = "14 shards + .ecsum block and leaf CRCs"


def needles_on_shard(c: Cluster, ev, shard_id: int) -> tuple[list[int], list[int]]:
    """Indices of written needles whose extent does / does not touch
    `shard_id` (the volume's own locate math)."""
    from seaweedfs_tpu.ec.decoder import record_actual_size
    from seaweedfs_tpu.ec.locate import locate_data
    from seaweedfs_tpu.ec.volume_info import VolumeInfo
    from seaweedfs_tpu.storage.file_id import FileId
    from seaweedfs_tpu.storage.types import actual_offset

    k = ev.ctx.data_shards
    shard_size = VolumeInfo.load(ev.base + ".vif").dat_file_size // k
    on, off = [], []
    for i, fid in enumerate(c.fids):
        nv = ev.find_needle(FileId.parse(fid).needle_id)
        check(nv is not None, f"needle {fid} missing from the .ecx")
        ivs = locate_data(
            actual_offset(nv.offset),
            record_actual_size(nv.size, ev.version),
            shard_size, k,
        )
        hit = any(iv.to_shard_and_offset(k)[0] == shard_id for iv in ivs)
        (on if hit else off).append(i)
    return on, off


def phase_degraded_read(c: Cluster, seed: int, facts: dict) -> None:
    """Take one data and one parity shard away; GETs of needles on the
    missing data shard must reconstruct on the device backend."""
    import grpc

    from seaweedfs_tpu.ec.backend import JaxBackend
    from seaweedfs_tpu.pb import cluster_pb2 as pb
    from seaweedfs_tpu.pb import rpc

    ev = c.vs.store.find_ec_volume(c.vid)
    check(ev is not None, "ec volume not mounted after ec.encode")
    check(isinstance(ev.backend, JaxBackend), f"EcVolume reads on {ev.backend!r}")
    # file first (still mounted and advertised), then unmount
    for sid in LOST_SHARDS:
        os.unlink(ev.base + ev.ctx.to_ext(sid))
    with grpc.insecure_channel(f"localhost:{c.vs.grpc_port}") as ch:
        rpc.volume_stub(ch).VolumeEcShardsUnmount(
            pb.EcShardsUnmountRequest(
                volume_id=c.vid, shard_ids=list(LOST_SHARDS)
            ),
            timeout=30,
        )
    on, off = needles_on_shard(c, ev, LOST_SHARDS[0])
    check(bool(on), f"no needle lies on shard {LOST_SHARDS[0]}")
    rng = np.random.default_rng([seed, 0xDE])
    rng.shuffle(on)
    rng.shuffle(off)
    on, off = on[:32], off[:16]
    before = ev.bytes_reconstructed
    t0 = time.perf_counter()
    got = read_back(c, seed, on + off)
    dt = time.perf_counter() - t0
    grew = ev.bytes_reconstructed - before
    check(grew > 0, "degraded reads reconstructed nothing on the device backend")
    facts.update(
        lost_shards=list(LOST_SHARDS), needles_on_lost_shard=len(on),
        needles_elsewhere=len(off), bytes=got,
        bytes_reconstructed=grew, backend=type(ev.backend).__name__,
        get_ms_mean=round(dt / (len(on) + len(off)) * 1e3, 2),
    )


def phase_rebuild(c: Cluster, seed: int, facts: dict) -> None:
    """Shell `ec.rebuild` regenerates both shards on the device."""
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as ctx

    # the master must have dropped the shards, or it reports no loss
    deadline = time.time() + 60
    while True:
        located = c.env.master.lookup_ec(c.vid, refresh=True)
        if not any(located.get(sid) for sid in LOST_SHARDS):
            break
        check(time.time() < deadline, "master still lists the lost shards")
        time.sleep(0.1)
    t0 = time.perf_counter()
    out = c.shell(f"ec.rebuild -volumeId {c.vid}")
    dt = time.perf_counter() - t0
    check(
        f"rebuilt shards {list(LOST_SHARDS)}" in out, f"ec.rebuild -> {out}"
    )
    base = os.path.join(c.data_dir, str(c.vid))
    ref_base = os.path.join(c.ref_dir, str(c.vid))
    same_encoding(base, ref_base, ctx, shard_ids=LOST_SHARDS)
    scrub = c.shell(f"ec.scrub -volumeId {c.vid}")
    check(
        f"{ctx.total} shards checked, 0 bitrot, 0 missing" in scrub,
        f"ec.scrub -> {scrub}",
    )
    sample = np.random.default_rng([seed, 0x4B]).choice(
        len(c.sizes), size=min(16, len(c.sizes)), replace=False
    )
    read_back(c, seed, [int(i) for i in sample])
    shard_bytes = os.path.getsize(base + ctx.to_ext(LOST_SHARDS[0]))
    facts.update(
        shell=out, bytes=shard_bytes * len(LOST_SHARDS),
        rebuild_mb_s=round(
            shard_bytes * ctx.data_shards / dt / 1e6, 1
        ),
        scrub=scrub.splitlines()[-1],
    )


def staged_case(backend, cpu, kind: str, data: np.ndarray) -> str:
    """One batch through the backend's staged surface vs CpuBackend:
    "exact", "mismatch", or raises what the device raised."""
    from seaweedfs_tpu.ec.backend import _decode_coeffs

    if kind == "encode":
        got = backend.to_host(backend.encode_staged(backend.to_device(data)))
        want = cpu.encode(data)
    else:
        k, total = cpu.ctx.data_shards, cpu.ctx.total
        src = tuple(i for i in range(total) if i not in LOST_SHARDS)[:k]
        coeffs = _decode_coeffs(cpu.matrix, k, LOST_SHARDS, src)
        got = backend.to_host(
            backend.apply_staged(coeffs, backend.to_device(data))
        )
        want = cpu.apply(coeffs, data)
    got = np.asarray(got)
    return "exact" if np.array_equal(got, want) else "mismatch"


def phase_staged_shapes(seed: int, shapes, facts: dict) -> None:
    """Batch shapes the 1 GiB volume cannot reach, on every impl. The
    impl the platform selects must be bit-exact; the others are
    reported and select nothing."""
    from seaweedfs_tpu.ec.backend import CpuBackend, JaxBackend, get_backend
    from seaweedfs_tpu.ec.context import ECContext

    results = {}
    for label, k, m, width, kind in shapes:
        ctx = ECContext(k, m)
        cpu = CpuBackend(ctx)
        selected = get_backend("tpu", k, m)
        data = np.random.default_rng([seed, k, width]).integers(
            0, 256, size=(k, width), dtype=np.uint8
        )
        row = {}
        for impl in IMPLS:
            if impl == selected._rs.impl:
                row[impl] = staged_case(selected, cpu, kind, data)
                check(
                    row[impl] == "exact",
                    f"{label}: selected impl {impl} is not bit-exact",
                )
                continue
            try:
                other = JaxBackend(ctx, impl=impl, n_devices=1)
                row[impl] = staged_case(other, cpu, kind, data)
            except Exception as e:  # reported, selects nothing
                row[impl] = f"{type(e).__name__}: {str(e)[:200]}"
        row["selected"] = selected._rs.impl
        results[label] = row
    facts["shapes"] = results


def phase_auto(seed: int, facts: dict) -> None:
    """`auto` in the process that holds the chip is the chip."""
    from seaweedfs_tpu.ec import backend as B

    be = B.get_backend("auto", 10, 4)
    check(isinstance(be, B.FallbackBackend), f"auto resolved to {be!r}")
    check(isinstance(be.primary, B.JaxBackend), f"auto primary {be.primary!r}")
    data = np.random.default_rng([seed, 0xA0]).integers(
        0, 256, size=(10, MIB), dtype=np.uint8
    )
    got = be.to_host(be.encode_staged(be.to_device(data)))
    check(
        np.array_equal(got, B.CpuBackend(be.ctx).encode(data)),
        "auto backend's parity differs from CpuBackend",
    )
    facts["fallbacks"] = check_no_fallback()


def check_no_fallback() -> list[dict]:
    """Every FallbackBackend alive served every batch on its device."""
    from seaweedfs_tpu.ec.backend import _FALLBACKS

    rows = []
    for be in list(_FALLBACKS):
        rows.append(
            {
                "primary": type(be.primary).__name__,
                "chip": be.chip_label,
                "fallback_batches": be.fallback_batches,
                "breaker": be.breaker.state,
            }
        )
        check(
            be.fallback_batches == 0 and be.breaker.state == "closed",
            f"a device backend gave way to the CPU: {rows[-1]}",
        )
    return rows


def run_one_chip(
    seed: int, meter: CompileMeter, volume_bytes: int = GIB,
    staged_shapes=STAGED_SHAPES,
) -> None:
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    cluster = None
    try:
        with phase("cluster", meter) as facts:
            cluster = Cluster(workdir)
            facts["data_dir"] = cluster.data_dir
        with phase("load", meter) as facts:
            phase_load(cluster, seed, volume_bytes, facts)
        with phase("encode", meter) as facts:
            phase_encode(cluster, facts)
        with phase("degraded_read", meter) as facts:
            phase_degraded_read(cluster, seed, facts)
        with phase("rebuild", meter) as facts:
            phase_rebuild(cluster, seed, facts)
        with phase("staged_shapes", meter) as facts:
            phase_staged_shapes(seed, staged_shapes, facts)
        with phase("auto", meter) as facts:
            phase_auto(seed, facts)
    finally:
        if cluster is not None:
            cluster.stop()
        shutil.rmtree(workdir, ignore_errors=True)


# ----------------------------------------------------- four-chip phases


def fabricate_volume(directory: str, vid: int, seed: int, nbytes: int) -> str:
    """A sealed volume of `nbytes` of seeded 1 MiB needles; its base."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.storage.volume import Volume

    vol = Volume(directory, vid, needle_map_kind="memory")
    for i in range(max(nbytes // BODY_BYTES, 1)):
        vol.write_needle(
            Needle(
                cookie=vid, needle_id=i + 1,
                data=body_of(seed, (vid << 20) + i, BODY_BYTES),
            )
        )
    vol.flush()
    base = vol.base_file_name(directory, "", vid)
    vol.close()
    return base


def encode_vs_cpu(base: str, ref_dir: str, backend) -> None:
    """`ec_encode_volume` on `backend` under the default placement
    policy, compared with a CpuBackend encode of the same volume."""
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as ctx
    from seaweedfs_tpu.ec.encoder import ec_encode_volume

    ref_base = os.path.join(ref_dir, os.path.basename(base))
    for ext in (".dat", ".idx"):
        os.link(base + ext, ref_base + ext)
    ec_encode_volume(base, ctx, backend)
    ec_encode_volume(ref_base, ctx, CpuBackend(ctx))
    same_encoding(base, ref_base, ctx)


def placements_since(n_before: int) -> list[str]:
    from seaweedfs_tpu.utils import trace

    roots = trace.traces(op="ec.encode_volume")[n_before:]
    return [
        ev["attrs"]["chip"]
        for r in roots
        for ev in span_events(r, "placement")
    ]


def run_four_chips(
    seed: int, meter: CompileMeter, wide_bytes: int = GIB,
    volume_bytes: int = 128 * MIB,
) -> None:
    """What exists only across chips: the column mesh (the Pallas kernel
    inside shard_map) under one wide stream, and the chip pool under two
    concurrent streams per chip (eight on four chips) — each against
    CpuBackend, with every chip seen to work."""
    from seaweedfs_tpu.ec.backend import JaxBackend, get_backend
    from seaweedfs_tpu.ec.chip_pool import pool_for
    from seaweedfs_tpu.utils import trace

    trace.configure(enabled=True)
    backend = get_backend("tpu", 10, 4)
    check(isinstance(backend, JaxBackend), f"tpu backend is {backend!r}")
    pool = pool_for(backend)
    check(pool is not None, "one device: no mesh and no chip pool")
    n_volumes = 2 * pool.n_chips
    workdir = tempfile.mkdtemp(prefix="chip_smoke4_")
    try:
        with phase("wide_stream_mesh", meter) as facts:
            d = os.path.join(workdir, "wide")
            os.makedirs(os.path.join(d, "ref"))
            base = fabricate_volume(d, 1, seed, wide_bytes)
            n0 = len(trace.traces(op="ec.encode_volume"))
            t0 = time.perf_counter()
            encode_vs_cpu(base, os.path.join(d, "ref"), backend)
            placed = placements_since(n0)
            check(placed == ["mesh"], f"wide stream placed on {placed}")
            check(not any(pool.loads()), f"pool load left: {pool.loads()}")
            facts.update(
                bytes=os.path.getsize(base + ".dat"), placement=placed,
                impl=backend._rs.impl,
                mesh_devices=list(backend._mesh_rs.device_labels()),
                encode_and_reference_seconds=round(
                    time.perf_counter() - t0, 3
                ),
                identical_to_cpu=True, pool_loads=pool.loads(),
            )
            shutil.rmtree(d)
        with phase("concurrent_streams_chips", meter) as facts:
            d = os.path.join(workdir, "many")
            os.makedirs(os.path.join(d, "ref"))
            bases = [
                fabricate_volume(d, vid, seed, volume_bytes)
                for vid in range(1, n_volumes + 1)
            ]
            n0 = len(trace.traces(op="ec.encode_volume"))
            with ThreadPoolExecutor(max_workers=n_volumes) as ex:
                list(
                    ex.map(
                        lambda b: encode_vs_cpu(
                            b, os.path.join(d, "ref"), backend
                        ),
                        bases,
                    )
                )
            placed = placements_since(n0)
            check(
                set(placed) == set(pool.labels),
                f"streams placed on {sorted(set(placed))}, "
                f"chips are {pool.labels}",
            )
            check(not any(pool.loads()), f"pool load left: {pool.loads()}")
            facts.update(
                volumes=n_volumes, bytes=n_volumes * volume_bytes,
                placement=sorted(placed), identical_to_cpu=True,
                pool_loads=pool.loads(),
            )
        peaks = peak_bytes_in_use()
        check(
            all(p is None or p > 0 for p in peaks)
            and len(peaks) == pool.n_chips,
            f"a chip held nothing: peak_bytes_in_use {peaks}",
        )
        check_no_fallback()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs the cross-chip paths only (column mesh, chip pool)",
    )
    a = ap.parse_args(argv)

    from seaweedfs_tpu.utils import devices

    info = devices.local_devices()
    if info.platform != "tpu":
        print(f"chip_smoke: no TPU: JAX reports {info}", file=sys.stderr)
        return 1
    if a.chips == 4 and info.count != 4:
        print(f"chip_smoke: --chips 4 needs 4 chips: {info}", file=sys.stderr)
        return 1
    import jax

    meter = CompileMeter()
    emit(
        {
            "phase": "devices", "platform": info.platform,
            "kind": info.kind, "count": info.count, "seed": a.seed,
            "jax": jax.__version__,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        }
    )
    if a.chips == 4:
        run_four_chips(a.seed, meter)
    else:
        run_one_chip(a.seed, meter)
    emit(
        {
            "ok": True,
            "device": {
                "platform": info.platform, "kind": info.kind,
                "count": info.count,
            },
        }
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
