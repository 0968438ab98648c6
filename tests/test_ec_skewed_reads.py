"""Skewed reads of an EC volume whose server is down (ISSUE 29): every
needle of a 10+4 volume asked for under YCSB-C's Zipf 0.99 while data
shards are gone, so healthy reads, interval-cache hits, waits on
another's build and reconstructions share one volume.

The reference of a GET is the body written under its file id (the
seeded volume of `ecbench/data.py`); the reference of a reconstructed
interval is `ecbench/reference_decode.py`'s RS decode from the shards
that are left, which imports nothing of the program; the traffic is the
cell's own sampler (`ecbench/drivers/http_gets_ycsb.py`). 8 MiB, CPU.
"""

from __future__ import annotations

import http.client
import os
import shutil
import threading
import time

import numpy as np
import pytest

from ecbench import data as D
from ecbench import harness
from ecbench import reference_decode
from seaweedfs_tpu.ec import CpuBackend, EcVolume, ec_encode_volume
from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as CTX
from seaweedfs_tpu.utils import metrics, trace

from test_trace import _NoClock

PLAN = {
    "large_body_bytes": 1 << 20, "small_per_gib": 300,
    "small_min_bytes": 1024, "small_max_bytes": 65536, "layout_seed": 24,
}
LAYOUT = {"data_shards": 10, "parity_shards": 4,
          "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20}
SEED = 2**31 + 29
SMALL_CACHE = 512 << 10  # under one lost shard's extents (about 1 MiB each)
PARTS = (
    "volume.read.index", "volume.read.shard", "volume.read.recover", "volume.read.parse",
)


@pytest.fixture(scope="module")
def ycsb():
    return harness.load_module("drivers", "http_gets_ycsb")


@pytest.fixture(scope="module")
def encoded(tmp_path_factory):
    """(the seeded volume, the bytes of each of its 14 shards)."""
    d = str(tmp_path_factory.mktemp("skewed"))
    vol = D.fabricate_volume(d, 1, SEED, 8 << 20, PLAN)
    ec_encode_volume(vol.base, CTX, CpuBackend(CTX))
    shards = []
    for i in range(CTX.total):
        with open(vol.base + CTX.to_ext(i), "rb") as f:
            shards.append(np.frombuffer(f.read(), dtype=np.uint8))
    return vol, shards


def open_degraded(vol, tmp_path, lost, cache_bytes, backend="cpu"):
    """The encoded volume's EC files under `tmp_path`, without the lost
    shards -> its EcVolume."""
    src = os.path.dirname(vol.base)
    for name in os.listdir(src):
        ext = os.path.splitext(name)[1]
        if ext in (".dat", ".idx") or name.endswith(tuple(CTX.to_ext(s) for s in lost)):
            continue
        shutil.copy(os.path.join(src, name), tmp_path / name)
    return EcVolume(
        str(tmp_path), vol.vid, backend_name=backend, interval_cache_bytes=cache_bytes
    )


def needles_on(ycsb, vol, lost) -> set[int]:
    return {
        i for sid in lost if sid < CTX.data_shards
        for i in ycsb.G.needles_on_shard(vol, sid, LAYOUT)
    }


def get(ev, vol, i) -> bytes:
    return ev.read_needle(i + 1, vol.cookie).data


def as_a_get(ev, vol, i) -> tuple[bytes, dict]:
    """One needle read under what the volume server's handler opens
    around it (an `http.volume` root, its `volume.read` stage) -> the
    body and the root's span document. The tracer is armed."""
    trace.reset()
    root = trace.start("http.volume", name=f"GET /{vol.fid(i)}", op_class="read")
    with trace.activate(root), trace.stage(root, "volume.read"):
        body = get(ev, vol, i)
    trace.finish(root)
    (doc,) = [d for d in trace.traces() if d["op"] == "http.volume"]
    return body, doc


def intervals(doc, stage):
    return [(t0, t1) for name, t0, t1, _th, _cpu in doc["intervals"] if name == stage]


# ------------------------------------------------ bytes, against references


@pytest.mark.parametrize("cache_bytes", [0, SMALL_CACHE], ids=["no_cache", "small_cache"])
@pytest.mark.parametrize("lost", [(1, 8), (3,), (1, 8, 11)], ids=lambda l: "lost_" + "_".join(map(str, l)))
def test_zipf_reads_over_all_needles_return_what_was_written(
    ycsb, encoded, tmp_path, disarmed, lost, cache_bytes
):
    vol, shards = encoded
    ev = open_degraded(vol, tmp_path, lost, cache_bytes)
    n = len(vol.sizes)
    on_lost = needles_on(ycsb, vol, lost)
    assert on_lost and len(on_lost) < n  # both kinds of needle are drawn
    built: list[tuple[int, int, bytes]] = []
    real = ev._reconstruct_range

    def recording(shard_id, offset, size, prot=None):
        out = real(shard_id, offset, size, prot)
        built.append((shard_id, offset, out))
        return out

    ev._reconstruct_range = recording
    by_rank = ycsb.popularity(n, 24).tolist()
    wrong: list[int] = []
    asked: set[int] = set()

    def client(w: int) -> None:
        ranks = ycsb.zipf_ranks(SEED, w, n, 0.99)
        for _ in range(40):
            i = by_rank[next(ranks)]
            asked.add(i)
            if get(ev, vol, i) != vol.body(i):
                wrong.append(i)

    threads = [threading.Thread(target=client, args=(w,)) for w in range(8)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        # the tail of the distribution, so that every needle is read once
        for i in range(n):
            assert get(ev, vol, i) == vol.body(i), i
        assert not wrong
        assert asked & on_lost and asked - on_lost
        # every reconstructed interval is the plain decode of the shards left
        want = reference_decode.decode(
            {s: shards[s] for s in range(CTX.total) if s not in lost},
            [s for s in lost if s < CTX.data_shards], CTX.data_shards, CTX.parity_shards,
        )
        assert built
        for shard_id, offset, out in built:
            assert shard_id in want
            assert out == want[shard_id][offset : offset + len(out)].tobytes()
            assert out == shards[shard_id][offset : offset + len(out)].tobytes()
        # a decode row per lost data shard; with a data shard among the
        # sources gone, parity rows stand in for it
        targets = {t for t, _src in ev._coeff_cache}
        assert targets == {s for s in lost if s < CTX.data_shards}
        for t, src in ev._coeff_cache:
            assert len(src) == CTX.data_shards and not set(src) & set(lost)
            assert sum(s >= CTX.data_shards for s in src) == sum(
                s < CTX.data_shards for s in lost
            )
        if cache_bytes:
            cache = ev.interval_cache
            assert 0 < cache.size_bytes <= cache_bytes
            assert cache.hits > 0 and cache.misses > 0  # hot keys hit, the rest evict
        else:
            assert ev.interval_cache is None
    finally:
        ev.close()


# --------------------------------------------- which reads recover, and how


def test_a_healthy_needle_recovers_nothing_and_a_needle_on_a_lost_shard_does(
    ycsb, encoded, tmp_path, disarmed
):
    vol, _shards = encoded
    ev = open_degraded(vol, tmp_path, (1, 8), SMALL_CACHE)
    on_lost = needles_on(ycsb, vol, (1, 8))
    healthy = next(i for i in range(len(vol.sizes)) if i not in on_lost)
    degraded = next(iter(sorted(on_lost)))
    siblings = []
    real = ev._sibling_matrix
    ev._sibling_matrix = lambda *a, **kw: siblings.append(a[:3]) or real(*a, **kw)
    trace.configure(enabled=True)
    try:
        read0 = ev.bytes_read
        body, doc = as_a_get(ev, vol, healthy)
        assert body == vol.body(healthy)
        assert doc["children"] == [] and not siblings
        lo, hi = vol.record_extent(healthy)
        assert ev.bytes_read - read0 == hi - lo  # its own record, no sibling's
        assert "volume.read.recover" not in doc["stages"]

        body, doc = as_a_get(ev, vol, degraded)
        assert body == vol.body(degraded)
        assert len(siblings) == 1
        (read,) = doc["children"]
        assert read["op"] == "ec.degraded_read" and "reconstruct" in read["stages"]
        assert read["attrs"]["shard"] in (1, 8)
        # the child span lies inside the part that recovers
        assert any(
            t0 <= read["start_ns"] and read["end_ns"] <= t1
            for t0, t1 in intervals(doc, "volume.read.recover")
        )
        # again: the interval cache answers, nothing is read or rebuilt
        body, doc = as_a_get(ev, vol, degraded)
        assert body == vol.body(degraded) and len(siblings) == 1
        (read,) = doc["children"]
        assert [e["name"] for e in read["events"]] == ["cache_hit"]
        assert "reconstruct" not in read["stages"]
    finally:
        trace.configure(enabled=False)
        ev.close()


def test_concurrent_gets_of_one_hot_degraded_needle_reconstruct_it_once(
    ycsb, encoded, tmp_path, disarmed
):
    vol, _shards = encoded
    ev = open_degraded(vol, tmp_path, (1, 8), 4 << 20)
    hot = max(needles_on(ycsb, vol, (1, 8)), key=lambda i: vol.sizes[i])
    calls = []
    real = ev._reconstruct_range

    def slow(*a, **kw):
        calls.append(a[:3])
        time.sleep(0.3)  # the others arrive while the first builds
        return real(*a, **kw)

    ev._reconstruct_range = slow
    bodies: list[bytes] = []
    start = threading.Barrier(16)

    def client() -> None:
        start.wait()
        bodies.append(get(ev, vol, hot))

    trace.configure(enabled=True)
    try:
        threads = [threading.Thread(target=client) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        assert len(calls) == 1
        assert len(bodies) == 16 and set(bodies) == {bytes(vol.body(hot))}
        reads = [d for d in trace.traces() if d["op"] == "ec.degraded_read"]
        assert len(reads) == 16
        built = [d for d in reads if "reconstruct" in d["stages"]]
        others = [d for d in reads if "reconstruct" not in d["stages"]]
        assert len(built) == 1 and len(others) == 15
        for d in others:
            (event,) = [e["name"] for e in d["events"]]
            assert event in ("singleflight_wait", "cache_hit")
        assert any(e["name"] == "singleflight_wait" for d in others for e in d["events"])
        cache = ev.interval_cache
        assert cache.loads == 1 and cache.singleflight_waits + cache.hits == 15
    finally:
        trace.configure(enabled=False)
        ev.close()


# ----------------------------------------------- the parts of `volume.read`


def test_the_parts_of_volume_read_lie_end_to_end_inside_it(ycsb, encoded, tmp_path, disarmed):
    vol, _shards = encoded
    ev = open_degraded(vol, tmp_path, (1, 8), SMALL_CACHE)
    on_lost = needles_on(ycsb, vol, (1, 8))
    trace.configure(enabled=True)
    try:
        for i in range(len(vol.sizes)):
            body, doc = as_a_get(ev, vol, i)
            assert body == vol.body(i)
            ((v0, v1),) = intervals(doc, "volume.read")
            parts = sorted(
                (t0, t1, name) for name in PARTS for t0, t1 in intervals(doc, name)
            )
            names = [name for _t0, _t1, name in parts]
            assert names[-1] == "volume.read.parse"
            assert ("volume.read.recover" in names) == (i in on_lost)
            assert "volume.read.shard" in names  # no record lies on one shard alone
            # end to end: each begins where the one before it ended, the
            # first after the stage began, the last ends with the stage
            assert v0 <= parts[0][0] and parts[-1][1] == v1
            for (_a0, a1, _an), (b0, _b1, _bn) in zip(parts, parts[1:]):
                assert a1 == b0
            st = doc["stages"]
            total = sum(st[p]["seconds"] for p in PARTS if p in st)
            assert total <= st["volume.read"]["seconds"] <= total + 0.005
            # a lap found `volume.read` open again after the child
            # span's own stages (sibling_read, crc_verify, reconstruct)
            # had closed: `.parse` is the root's, not the child's
            for child in doc["children"]:
                assert not set(PARTS) & set(child["stages"])
        # the sums over stages count parents only
        assert set(PARTS) <= trace.SUB_STAGES <= trace.STAGES
    finally:
        trace.configure(enabled=False)
        ev.close()


def test_a_traced_server_records_the_parts_under_its_http_roots(ycsb, encoded, tmp_path, disarmed):
    """Through the normal path: `GET /<fid>` on a volume server's public
    port, shards 1 and 8 unlinked and unmounted after shell `ec.encode`."""
    from ecbench import cluster as C
    from ecbench import reference as R

    vol_dir = str(tmp_path / "vol")
    os.makedirs(vol_dir)
    vol = D.fabricate_volume(vol_dir, 1, SEED, 8 << 20, PLAN)
    cl = C.Cluster(vol_dir, {"ec_backend": "cpu", "ec_interval_cache_mb": 1}, True)
    try:
        cl.wait_volume_listed(vol.vid)
        cl.shell(f"ec.encode -volumeId {vol.vid}")
        for sid in (1, 8):
            os.unlink(vol.base + R.shard_ext(sid))
        cl.unmount_shards(vol.vid, (1, 8))
        on_lost = needles_on(ycsb, vol, (1, 8))
        healthy = next(i for i in range(len(vol.sizes)) if i not in on_lost)
        degraded = next(iter(sorted(on_lost)))
        trace.reset()
        peer_reads_before = metrics.ec_peer_reads_total.snapshot()
        conn = http.client.HTTPConnection(*cl.volume_host, timeout=30)
        for i in (healthy, degraded):
            status, body = ycsb.G._get(conn, vol.fid(i))
            assert status == 200 and body == vol.body(i)
        conn.close()
        # an HTTP root is recorded after its response has left
        deadline = time.time() + 10
        while True:
            roots = {
                d["name"]: d for d in trace.traces()
                if d["op"] == "http.volume" and d["attrs"].get("op_class") == "read"
            }
            if len(roots) == 2 or time.time() > deadline:
                break
            time.sleep(0.02)
        plain, recovering = roots[f"GET /{vol.fid(healthy)}"], roots[f"GET /{vol.fid(degraded)}"]
        assert plain["children"] == []
        assert {"volume.read", "volume.read.shard", "volume.read.parse"} <= set(plain["stages"])
        assert "volume.read.recover" not in plain["stages"]
        (read,) = recovering["children"]
        assert read["op"] == "ec.degraded_read"
        assert any(
            t0 <= read["start_ns"] and read["end_ns"] <= t1
            for t0, t1 in intervals(recovering, "volume.read.recover")
        )
        for doc in (plain, recovering):
            st = doc["stages"]
            total = sum(st[p]["seconds"] for p in PARTS if p in st)
            assert 0.5 * st["volume.read"]["seconds"] <= total <= st["volume.read"]["seconds"]
        # no peer holds a shard of this volume: the lost shards are looked
        # up and nobody is asked, so nothing is booked as a peer's read
        for doc in (plain, recovering, read):
            assert not {"peer_read", "volume.read.peer"} & set(doc["stages"])
            assert not {"peer_reads", "peer_fetches_started"} & set(doc["attrs"])
        assert metrics.ec_peer_reads_total.snapshot() == peer_reads_before
    finally:
        cl.stop()


def test_disarmed_the_marks_read_no_clock_and_allocate_nothing(
    ycsb, encoded, tmp_path, disarmed, monkeypatch
):
    vol, _shards = encoded
    ev = open_degraded(vol, tmp_path, (1, 8), SMALL_CACHE)
    on_lost = needles_on(ycsb, vol, (1, 8))
    try:
        assert not trace.armed
        assert trace.lap("shard") is None and trace.lap("recover") is None
        assert trace.lap("parse") is None
        monkeypatch.setattr(trace, "time", _NoClock())
        monkeypatch.setattr(trace, "_StageTimer", None)  # constructing one fails
        monkeypatch.setattr(trace, "_annotate", None)
        monkeypatch.setattr(trace, "Span", None)
        for i in range(len(vol.sizes)):
            assert get(ev, vol, i) == vol.body(i)
        assert ev.bytes_reconstructed > 0 and on_lost
        assert trace.traces() == []
    finally:
        ev.close()
