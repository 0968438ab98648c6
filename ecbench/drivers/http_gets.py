"""Closed-loop HTTP GETs on the volume server's public port.

Traffic parameters (traffic/<name>.json):
  clients      closed-loop clients, each on one keep-alive connection
               (upstream `weed benchmark -c 16`)
  lost_shards  shards unlinked and unmounted after the volume is encoded
  select       which needles the clients ask for; each client draws
               uniformly among them from a seeded stream of its own
               (upstream `weed benchmark`'s random reads):
                 "extent_on_lost_data_shard"  needles with bytes on the
                     first lost data shard: every GET reconstructs

Latency is send to last byte on the client's clock. Every body is
compared with the seeded body of its file id as it arrives, after the
clock has stopped. No GET starts after `seconds`; those in flight
finish and count.
"""

from __future__ import annotations

import dataclasses
import http.client
import os
import statistics
import sys
import threading
import time

import numpy as np

from ecbench import cluster as C
from ecbench import data as D
from ecbench import reference as R
from ecbench.harness import Compared, Observed, annotate

GET_TIMEOUT_S = 60.0


@dataclasses.dataclass
class State:
    cluster: C.Cluster
    volume: D.SeededVolume
    targets: list[int]  # needle indices the clients draw from
    ev: object  # the mounted EcVolume, for its counters


def needles_on_shard(vol: D.SeededVolume, shard_id: int, layout: dict) -> list[int]:
    """Needles whose record has bytes on data shard `shard_id`, by the
    striping the configuration states (rows of k large blocks while a
    whole row is left, then rows of k small blocks)."""
    k = int(layout["data_shards"])
    large, small = int(layout["large_block_bytes"]), int(layout["small_block_bytes"])
    large_end = (vol.dat_bytes // (large * k)) * large * k

    def shards_of(lo: int, hi: int) -> set[int]:
        out = set()
        pos = lo
        while pos < hi:
            if pos < large_end:
                block, origin = large, 0
            else:
                block, origin = small, large_end
            b = (pos - origin) // block
            out.add(b % k)
            pos = origin + (b + 1) * block
        return out

    return [
        i for i in range(len(vol.sizes))
        if shard_id in shards_of(*vol.record_extent(i))
    ]


def client_draws(seed: int, client: int, n: int):
    """Client `client`'s endless stream of uniform draws from range(n)."""
    rng = np.random.default_rng([seed, 0xC11E, client])
    while True:
        yield from rng.integers(0, n, size=1024).tolist()


def _get(conn: http.client.HTTPConnection, fid: str) -> tuple[int, bytes]:
    conn.request("GET", f"/{fid}")
    resp = conn.getresponse()
    return resp.status, resp.read()


def _sweep(st: State, indices: list[int], clients: int) -> None:
    """One untimed GET of every needle in `indices`, over `clients`
    connections: compiles every extent width the window can meet."""
    errors: list[str] = []

    def work(w: int) -> None:
        conn = http.client.HTTPConnection(*st.cluster.volume_host, timeout=GET_TIMEOUT_S)
        try:
            for i in indices[w::clients]:
                status, body = _get(conn, st.volume.fid(i))
                if status != 200 or body != st.volume.body(i):
                    errors.append(f"warm-up GET {st.volume.fid(i)} -> {status}")
        finally:
            conn.close()

    threads = [threading.Thread(target=work, args=(w,)) for w in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise C.BenchError(f"{len(errors)} warm-up GETs failed, first: {errors[0]}")


def setup(cell) -> State:
    cfg, traffic = cell.config, cell.traffic
    layout = cfg["layout"]
    k = int(layout["data_shards"])
    vol_dir = os.path.join(cell.data_dir, "vol")
    os.makedirs(vol_dir)
    vol = D.fabricate_volume(vol_dir, 1, cell.seed, int(cfg["volume_bytes"]), cfg["needles"])
    cell.mark("volume")
    cl = C.Cluster(vol_dir, cfg, cell.traced)
    cl.wait_volume_listed(vol.vid)
    cell.mark("cluster")
    cl.shell(f"ec.encode -volumeId {vol.vid}")
    cell.mark("encoded")
    lost = tuple(int(s) for s in traffic.get("lost_shards", ()))
    ev = cl.vs.store.find_ec_volume(vol.vid)
    if ev is None:
        raise C.BenchError("the EC volume is not mounted after ec.encode")
    # file first (still mounted and advertised), then unmount
    for sid in lost:
        os.unlink(vol.base + R.shard_ext(sid))
    if lost:
        cl.unmount_shards(vol.vid, lost)
    select = traffic["select"]
    if select != "extent_on_lost_data_shard":
        raise C.BenchError(f"http_gets knows no selection {select!r}")
    lost_data = [s for s in lost if s < k]
    if not lost_data:
        raise C.BenchError(f"{select} needs a lost data shard, lost are {lost}")
    targets = needles_on_shard(vol, lost_data[0], layout)
    if not targets:
        raise C.BenchError("the selection holds no needle")
    st = State(cluster=cl, volume=vol, targets=targets, ev=ev)
    _sweep(st, targets, int(traffic["clients"]))
    cell.mark(f"swept_{len(targets)}_targets")
    os.sync()  # the set-up's write-back must not land in the window
    return st


def _cache_counts(st: State) -> tuple[int, int]:
    cache = st.ev.interval_cache
    return (int(cache.hits), int(cache.misses)) if cache is not None else (0, 0)


def window(cell, st: State, slice_) -> Observed:
    clients = int(cell.traffic["clients"])
    obs = Observed()
    lock = threading.Lock()
    wrong: list[str] = []
    failed: list[str] = []
    hits0, misses0 = _cache_counts(st)
    rec0 = int(st.ev.bytes_reconstructed)
    t_begin = time.perf_counter()
    deadline = t_begin + cell.seconds
    stop_ticks = threading.Event()

    def ticks() -> None:
        while not stop_ticks.wait(0.05):
            slice_.boundary()

    def work(w: int) -> None:
        conn = http.client.HTTPConnection(*st.cluster.volume_host, timeout=GET_TIMEOUT_S)
        mine: list[tuple[float, float]] = []
        draws = client_draws(cell.seed, w, len(st.targets))
        try:
            while True:
                i = st.targets[next(draws)]
                fid = st.volume.fid(i)
                t0 = time.perf_counter()
                if t0 >= deadline:
                    break
                try:
                    with annotate("ecbench.get"):
                        status, body = _get(conn, fid)
                    t1 = time.perf_counter()
                except (OSError, http.client.HTTPException) as e:
                    failed.append(f"GET {fid}: {type(e).__name__}: {e}")
                    conn.close()
                    conn = http.client.HTTPConnection(
                        *st.cluster.volume_host, timeout=GET_TIMEOUT_S
                    )
                    continue
                if status != 200 or body != st.volume.body(i):
                    wrong.append(f"GET {fid} -> {status}, {len(body)} bytes")
                mine.append((t0, t1))
        finally:
            conn.close()
            with lock:
                obs.gets.extend(mine)

    threads = [threading.Thread(target=work, args=(w,), daemon=True) for w in range(clients)]
    ticker = threading.Thread(target=ticks, daemon=True)
    ticker.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop_ticks.set()
    ticker.join()
    slice_.close()
    if not obs.gets:
        raise C.BenchError(f"no GET came back; first failure: {(failed or wrong or ['none'])[0]}")
    hits1, misses1 = _cache_counts(st)
    obs.t_start = t_begin
    obs.t_end = max(t1 for _t0, t1 in obs.gets)
    obs.attempted = len(obs.gets) + len(failed)
    obs.failed = len(failed) + len(wrong)
    obs.bytes = 0
    lat_ms = sorted((t1 - t0) * 1e3 for t0, t1 in obs.gets)
    good = len(obs.gets) - len(wrong)
    obs.end_to_end["fg_p50_ms"] = statistics.median(lat_ms)
    obs.end_to_end["fg_p95_ms"] = lat_ms[min(int(0.95 * len(lat_ms)), len(lat_ms) - 1)]
    obs.end_to_end["fg_ops_per_s"] = good / (obs.t_end - obs.t_start)
    obs.counters.update(
        cache_hits=hits1 - hits0, cache_misses=misses1 - misses0,
        bytes_reconstructed=int(st.ev.bytes_reconstructed) - rec0,
    )
    obs.notes.update(wrong=wrong, failed=failed)
    return obs


def verify(cell, st: State, obs: Observed, control: bool = False) -> list[Compared]:
    """Every GET of the window was compared with its seeded body as it
    came; here the counts are held to their limits. With `control` every
    target is answered with the body of the file id next to it (an
    answer that says the wrong thing where the exact one is due) and
    the same comparison has to fail."""
    layout = cell.config["layout"]
    k, m = int(layout["data_shards"]), int(layout["parity_shards"])
    wrong, failed = len(obs.notes["wrong"]), len(obs.notes["failed"])
    if control:
        n = len(st.volume.sizes)
        wrong = sum(
            1 for i in st.targets
            if bytes(st.volume.body((i + 1) % n)) != st.volume.body(i)
        )
    not_device, fallen = st.cluster.backend_faults(k, m)
    reconstructs = obs.counters["bytes_reconstructed"] <= 0
    for line in (obs.notes["wrong"] + obs.notes["failed"])[:5]:
        print(f"ecbench: {line}", flush=True, file=sys.stderr)
    return [
        Compared("gets_compared", len(obs.gets), None),
        Compared("gets_wrong", wrong, 0),
        Compared("gets_failed", failed, 0),
        Compared("nothing_reconstructed", int(reconstructs), 0),
        Compared("backend_not_on_device", not_device, 0),
        Compared("fallback_batches", fallen, 0),
    ]


def teardown(st: State) -> None:
    st.cluster.stop()
