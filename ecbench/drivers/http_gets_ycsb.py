"""Closed-loop HTTP GETs over ALL needles of a volume that has lost
shards, popularity skewed as YCSB core workload C has it: reads only,
ranks drawn from a Zipf distribution, ranks scrambled over the keys.
Healthy GETs, interval-cache hits and reconstructions share one queue.

Traffic parameters (traffic/<name>.json):
  clients               closed-loop clients, one keep-alive connection each
  read_proportion       1.0: the driver sends nothing but GETs
  keys                  "all": every needle of the volume is a key
  request_distribution  "zipfian": rank r is drawn with probability
                        proportional to r ** -zipfian_constant
  zipfian_constant      YCSB's ZipfianGenerator constant, 0.99
  popularity_seed       fixes the permutation from rank to needle (it
                        stands for YCSB's hash scramble), so every run
                        seed meets the same hot set
  warm_draws            untimed draws sent before the window, so that
                        the window opens on the cache's steady state

The lost shards are the configuration's (`lost_shards`): they say which
server is down, and that belongs to the deployment. Client w draws its
ranks from a stream seeded by (run seed, w); the run's seed also fills
the bodies. What `http_gets` exports is used as it stands: its state,
`needles_on_shard`, `_get`, `_sweep`, the cache's counters, its
comparison and its control.
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
from ecbench.harness import Compared, Observed, annotate, load_module

G = load_module("drivers", "http_gets")

WARM_CLIENT = 0xAA  # the warm-up's stream: no client of the window has it


@dataclasses.dataclass
class State(G.State):
    on_lost: frozenset = frozenset()  # needles with bytes on a lost data shard


def zipf_cdf(n: int, theta: float) -> np.ndarray:
    """P(rank <= r) for r = 1..n where P(rank = r) is proportional to
    r ** -theta: the closed form the sampler is held to."""
    weights = np.arange(1, n + 1, dtype=np.float64) ** -theta
    return np.cumsum(weights) / weights.sum()


def popularity(n: int, popularity_seed: int) -> np.ndarray:
    """Needle index of each rank (0 = the hottest): a permutation that
    depends on `popularity_seed` alone."""
    return np.random.default_rng([int(popularity_seed), 0x2C5B]).permutation(n)


def zipf_ranks(seed: int, client: int, n: int, theta: float):
    """Client `client`'s endless stream of ranks in range(n), 0 the
    hottest: inverse CDF on a generator seeded by (seed, client)."""
    cdf = zipf_cdf(n, theta)
    rng = np.random.default_rng([int(seed), 0x2C5C, int(client)])
    while True:
        # a draw of exactly 1.0 cannot happen (random() < 1); the clip
        # guards the last rank against the CDF's rounding
        yield from np.minimum(np.searchsorted(cdf, rng.random(1024)), n - 1).tolist()


def needle_stream(cell, client: int, n: int):
    """Client `client`'s endless stream of needle indices."""
    t = cell.traffic
    by_rank = popularity(n, t["popularity_seed"]).tolist()
    for rank in zipf_ranks(cell.seed, client, n, float(t["zipfian_constant"])):
        yield by_rank[rank]


def _sweep(st: State, indices: list[int], clients: int) -> None:
    """`http_gets._sweep`, and where a GET of it failed, the server's
    own words for the first that fails again: a reconstruction that the
    `.ecsum` sidecar does not bear out is refused, never served, and
    the run then has no result at all."""
    try:
        G._sweep(st, indices, clients)
    except C.BenchError as e:
        conn = http.client.HTTPConnection(*st.cluster.volume_host, timeout=G.GET_TIMEOUT_S)
        try:
            for i in indices:
                status, body = G._get(conn, st.volume.fid(i))
                if status == 200:
                    continue
                said = body[:300].decode(errors="replace")
                if ".ecsum verification" in said:
                    said += " (what it reconstructed fails sidecar verification: refused, not served)"
                raise C.BenchError(f"{e}; GET {st.volume.fid(i)} again -> {status} {said}") from e
        finally:
            conn.close()
        raise


def setup(cell) -> State:
    cfg, traffic = cell.config, cell.traffic
    if (traffic["request_distribution"], traffic["keys"]) != ("zipfian", "all"):
        raise C.BenchError("http_gets_ycsb draws zipfian ranks over all keys, nothing else")
    if float(traffic["read_proportion"]) != 1.0:
        raise C.BenchError("http_gets_ycsb sends reads only (YCSB workload C)")
    layout = cfg["layout"]
    k = int(layout["data_shards"])
    clients = int(traffic["clients"])
    vol_dir = os.path.join(cell.data_dir, "vol")
    os.makedirs(vol_dir)
    vol = D.fabricate_volume(vol_dir, 1, cell.seed, int(cfg["volume_bytes"]), cfg["needles"])
    cell.mark("volume")
    cl = C.Cluster(vol_dir, cfg, cell.traced)
    cl.wait_volume_listed(vol.vid)
    cell.mark("cluster")
    cl.shell(f"ec.encode -volumeId {vol.vid}")
    cell.mark("encoded")
    lost = tuple(int(s) for s in cfg["lost_shards"])
    ev = cl.vs.store.find_ec_volume(vol.vid)
    if ev is None:
        raise C.BenchError("the EC volume is not mounted after ec.encode")
    # file first (still mounted and advertised), then unmount
    for sid in lost:
        os.unlink(vol.base + R.shard_ext(sid))
    cl.unmount_shards(vol.vid, lost)
    on_lost = sorted({
        i for sid in lost if sid < k for i in G.needles_on_shard(vol, sid, layout)
    })
    if not on_lost or len(on_lost) == len(vol.sizes):
        raise C.BenchError(
            f"{len(on_lost)} of {len(vol.sizes)} needles have bytes on the lost data "
            f"shards of {lost}: the cell needs needles on them and needles off them"
        )
    st = State(
        cluster=cl, volume=vol, targets=list(range(len(vol.sizes))), ev=ev,
        on_lost=frozenset(on_lost),
    )
    # every extent width the window can meet compiles here
    _sweep(st, on_lost, clients)
    cell.mark(f"swept_{len(on_lost)}_on_lost_shards_of_{len(st.targets)}")
    warm = needle_stream(cell, WARM_CLIENT, len(st.targets))
    _sweep(st, [next(warm) for _ in range(int(traffic["warm_draws"]))], clients)
    cell.mark("warmed")
    os.sync()  # the set-up's write-back must not land in the window
    return st


def _p50_p95(ms: list[float]) -> tuple[float, float]:
    ms = sorted(ms)
    return statistics.median(ms), ms[min(int(0.95 * len(ms)), len(ms) - 1)]


def window(cell, st: State, slice_) -> Observed:
    clients = int(cell.traffic["clients"])
    obs = Observed()
    lock = threading.Lock()
    wrong: list[str] = []
    failed: list[str] = []
    gets: list[tuple[float, float, bool]] = []  # (t0, t1, needle on a lost shard)
    hits0, misses0 = G._cache_counts(st)
    rec0 = int(st.ev.bytes_reconstructed)
    t_begin = time.perf_counter()
    deadline = t_begin + cell.seconds
    stop_ticks = threading.Event()

    def ticks() -> None:
        while not stop_ticks.wait(0.05):
            slice_.boundary()

    def work(w: int) -> None:
        conn = http.client.HTTPConnection(*st.cluster.volume_host, timeout=G.GET_TIMEOUT_S)
        mine: list[tuple[float, float, bool]] = []
        draws = needle_stream(cell, w, len(st.targets))
        try:
            while True:
                i = next(draws)
                fid = st.volume.fid(i)
                t0 = time.perf_counter()
                if t0 >= deadline:
                    break
                try:
                    with annotate("ecbench.get"):
                        status, body = G._get(conn, fid)
                    t1 = time.perf_counter()
                except (OSError, http.client.HTTPException) as e:
                    failed.append(f"GET {fid}: {type(e).__name__}: {e}")
                    conn.close()
                    conn = http.client.HTTPConnection(
                        *st.cluster.volume_host, timeout=G.GET_TIMEOUT_S
                    )
                    continue
                if status != 200 or body != st.volume.body(i):
                    wrong.append(f"GET {fid} -> {status}, {len(body)} bytes")
                mine.append((t0, t1, i in st.on_lost))
        finally:
            conn.close()
            with lock:
                gets.extend(mine)

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
    obs.gets = [(t0, t1) for t0, t1, _lost in gets]
    if not obs.gets:
        raise C.BenchError(f"no GET came back; first failure: {(failed or wrong or ['none'])[0]}")
    hits1, misses1 = G._cache_counts(st)
    obs.t_start = t_begin
    obs.t_end = max(t1 for _t0, t1 in obs.gets)
    obs.attempted = len(obs.gets) + len(failed)
    obs.failed = len(failed) + len(wrong)
    obs.bytes = 0
    good = len(obs.gets) - len(wrong)
    obs.end_to_end["fg_p50_ms"], obs.end_to_end["fg_p95_ms"] = _p50_p95(
        [(t1 - t0) * 1e3 for t0, t1 in obs.gets]
    )
    obs.end_to_end["fg_ops_per_s"] = good / (obs.t_end - obs.t_start)
    has_cache = st.ev.interval_cache is not None
    n_lost = sum(1 for _t0, _t1, lost in gets if lost)
    obs.counters.update(
        cache_hits=hits1 - hits0, cache_misses=misses1 - misses0,
        bytes_reconstructed=int(st.ev.bytes_reconstructed) - rec0,
        gets_on_lost_shard=n_lost,
        # with no interval cache every GET on a lost shard reconstructs
        gets_reconstructing=misses1 - misses0 if has_cache else n_lost,
    )
    obs.notes.update(wrong=wrong, failed=failed)
    # the two modes apart, on the client's clock: which of them a
    # percentile of the whole window reads
    for name, want in (("off the lost shards", False), ("on a lost shard", True)):
        ms = [(t1 - t0) * 1e3 for t0, t1, lost in gets if lost is want]
        if ms:
            p50, p95 = _p50_p95(ms)
            print(
                f"ecbench: GETs of needles {name}: {len(ms)}, p50 {p50:.2f} ms, p95 {p95:.2f} ms",
                file=sys.stderr, flush=True,
            )
    return obs


def verify(cell, st: State, obs: Observed, control: bool = False) -> list[Compared]:
    """`http_gets`' comparison (every body of the window against the
    seeded body of its file id as it came; with `control` every key
    answered with its neighbour's body) and, beside it, that the window
    mixed what the cell is for: GETs that reconstructed nothing."""
    compared = G.verify(cell, st, obs, control=control)
    healthy = len(obs.gets) - obs.counters["gets_on_lost_shard"]
    return compared + [
        Compared("gets_on_lost_shard", obs.counters["gets_on_lost_shard"], None),
        Compared("gets_reconstructing", obs.counters["gets_reconstructing"], None),
        Compared("no_healthy_get", int(healthy <= 0), 0),
    ]


teardown = G.teardown
