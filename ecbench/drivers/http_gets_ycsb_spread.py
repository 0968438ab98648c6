"""YCSB-C GETs over ALL needles of a volume whose shards lie where
upstream leaves them: spread over seven volume servers, one of them
dead. A GET enters at any live server, which reads the intervals of
the needle from its own two shards, from its peers over
`VolumeEcShardRead`, and those on a lost shard by a reconstruction
whose sibling rows it gathers from its peers.

Traffic parameters (traffic/<name>.json), beside `http_gets_ycsb`'s:
  entry_server  "uniform_live_holder": per request, the server a client
                sends its GET to is drawn uniformly among the live
                servers, from a stream seeded by (run seed, client)
                that is independent of the key's stream
  warm_sweep    "on_lost_through_each_live_server": every needle with
                bytes on a lost data shard, once through EACH live
                server, untimed: every extent width compiles and every
                server's interval cache has seen its share
  warm_draws    untimed draws (key and entry server as in the window)
  down_server   the server that set-up stops after the spread; never
                restarted (the configuration's `down_server`)

Set-up: the volume is fabricated in server 0's directory, the shell's
`ec.encode` leaves all 14 shards there, and every other server gets its
shards by the RPCs the shell's `ec.balance` executes for a move. Then
the dead server is stopped: its files stay, no file is unlinked, and
set-up waits until the master lists its shards nowhere.

The window's rules, the three `fg_*` metrics and the comparison of
bodies are `http_gets_ycsb`'s and `http_gets`'; the client loop is this
driver's own, since it draws a server per request. Each client keeps
one keep-alive connection to EACH live server.

Beside them `correct` rests on `no_peer_read`: the HOLDERS sent bytes
for shard ranges in the window, whichever plane carried them.
`peer_bytes_served` = `peer_bytes_served_stream` (the
`VolumeEcShardRead` streams) + `peer_bytes_served_plane` (the live
servers' native shard planes, `VolumeServer.net_plane`); `ServedMeter`
says how each is made up. The READERS' count of the same bytes
(`peer_reader_bytes`) stands beside the sum.
"""

from __future__ import annotations

import dataclasses
import http.client
import os
import sys
import threading
import time

import numpy as np

from ecbench import cluster as C
from ecbench import data as D
from ecbench.harness import Compared, Observed, annotate, load_module
from ecbench.spread_cluster import SpreadCluster

Y = load_module("drivers", "http_gets_ycsb")
G = Y.G


@dataclasses.dataclass
class State:
    cluster: SpreadCluster
    volume: D.SeededVolume
    targets: list[int]  # needle indices the clients draw from: all
    on_lost: frozenset  # needles with bytes on a lost data shard
    live: list[int]  # the servers a GET may enter at
    evs: list  # the mounted EcVolume of each live server, for its counters


def entry_draws(seed: int, client: int, n_live: int):
    """Client `client`'s endless stream of positions in the list of live
    servers: uniform, seeded by (seed, client), and on another stream
    than the client's keys."""
    rng = np.random.default_rng([int(seed), 0x5E77, int(client)])
    while True:
        yield from rng.integers(0, n_live, size=1024).tolist()


def _connect(st: State) -> dict[int, http.client.HTTPConnection]:
    return {
        s: http.client.HTTPConnection(*st.cluster.host(s), timeout=G.GET_TIMEOUT_S)
        for s in st.live
    }


def _sweep(st: State, asks: list[tuple[int, int]], clients: int) -> None:
    """One untimed GET of every (server, needle) in `asks`, over
    `clients` clients; where one fails, the server's own words for it:
    a reconstruction that the `.ecsum` sidecar does not bear out is
    refused, never served, and the run then has no result at all."""
    errors: list[str] = []

    def work(w: int) -> None:
        conns = _connect(st)
        try:
            for s, i in asks[w::clients]:
                status, body = G._get(conns[s], st.volume.fid(i))
                if status == 200 and body == st.volume.body(i):
                    continue
                said = body[:300].decode(errors="replace") if status != 200 else "a wrong body"
                if ".ecsum verification" in said:
                    said += " (what it reconstructed fails sidecar verification: refused, not served)"
                errors.append(f"warm-up GET {st.volume.fid(i)} at server {s} -> {status} {said}")
        except (OSError, http.client.HTTPException) as e:
            errors.append(f"warm-up GETs of client {w}: {type(e).__name__}: {e}")
        finally:
            for c in conns.values():
                c.close()

    threads = [threading.Thread(target=work, args=(w,)) for w in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise C.BenchError(f"{len(errors)} warm-up GETs failed, first: {errors[0]}")


def setup(cell) -> State:
    cfg, traffic = cell.config, cell.traffic
    if (traffic["request_distribution"], traffic["keys"]) != ("zipfian", "all"):
        raise C.BenchError("http_gets_ycsb_spread draws zipfian ranks over all keys")
    if float(traffic["read_proportion"]) != 1.0:
        raise C.BenchError("http_gets_ycsb_spread sends reads only (YCSB workload C)")
    if traffic["entry_server"] != "uniform_live_holder":
        raise C.BenchError(f"no entry rule {traffic['entry_server']!r}")
    if traffic["warm_sweep"] != "on_lost_through_each_live_server":
        raise C.BenchError(f"no warm sweep {traffic['warm_sweep']!r}")
    layout = cfg["layout"]
    k = int(layout["data_shards"])
    clients = int(traffic["clients"])
    placed = cfg["placement"]["shards_of_server"]
    down = int(traffic["down_server"])
    lost = tuple(int(s) for s in cfg["lost_shards"])
    if down != int(cfg["down_server"]) or sorted(placed[down]) != sorted(lost):
        raise C.BenchError(
            f"the traffic stops server {down}, the configuration loses shards {lost} "
            f"with server {cfg['down_server']}"
        )
    vol_dir = os.path.join(cell.data_dir, "vs0")
    os.makedirs(vol_dir)
    vol = D.fabricate_volume(vol_dir, 1, cell.seed, int(cfg["volume_bytes"]), cfg["needles"])
    cell.mark("volume")
    cl = SpreadCluster(cell.data_dir, cfg, cell.traced, servers=len(placed))
    st = State(cluster=cl, volume=vol, targets=list(range(len(vol.sizes))),
               on_lost=frozenset(), live=[], evs=[])
    try:
        cl.wait_volume_listed(vol.vid)
        cell.mark("cluster")
        cl.shell(f"ec.encode -volumeId {vol.vid}")
        cell.mark("encoded")
        for dst in range(1, len(placed)):
            cl.move_shards(vol.vid, 0, dst, placed[dst])
        cl.wait_placement(vol.vid, placed)
        cell.mark("spread")
        cl.stop_server(down)
        cl.wait_placement(vol.vid, placed)  # the dead server's shards: nowhere
        cell.mark("server_down")
        st.live = cl.live()
        st.evs = [cl.servers[s].store.find_ec_volume(vol.vid) for s in st.live]
        if None in st.evs:
            raise C.BenchError("a live server has not mounted the EC volume")
        on_lost = sorted({
            i for sid in lost if sid < k for i in G.needles_on_shard(vol, sid, layout)
        })
        if not on_lost or len(on_lost) == len(vol.sizes):
            raise C.BenchError(
                f"{len(on_lost)} of {len(vol.sizes)} needles have bytes on the lost data "
                f"shards of {lost}: the cell needs needles on them and needles off them"
            )
        st.on_lost = frozenset(on_lost)
        # every extent width the window can meet compiles here, and every
        # server's reader has looked the shards up after the death
        _sweep(st, [(s, i) for i in on_lost for s in st.live], clients)
        cell.mark(f"swept_{len(on_lost)}_on_lost_shards_through_{len(st.live)}_servers")
        warm_keys = Y.needle_stream(cell, Y.WARM_CLIENT, len(st.targets))
        warm_entry = entry_draws(cell.seed, Y.WARM_CLIENT, len(st.live))
        _sweep(
            st,
            [(st.live[next(warm_entry)], next(warm_keys))
             for _ in range(int(traffic["warm_draws"]))],
            clients,
        )
        cell.mark("warmed")
        cl.arm_tracer()
    except BaseException:
        cl.stop()
        raise
    os.sync()  # the set-up's write-back must not land in the window
    return st


def _cache_counts(st: State) -> tuple[int, int]:
    """(hits, misses) summed over the live servers' interval caches."""
    caches = [ev.interval_cache for ev in st.evs if ev.interval_cache is not None]
    return sum(int(c.hits) for c in caches), sum(int(c.misses) for c in caches)


def _reconstructed(st: State) -> int:
    return sum(int(ev.bytes_reconstructed) for ev in st.evs)


def _python_plane_sent() -> int:
    """Bytes the process has sent on the Python byte plane for reads
    (`sw_net_bytes_sent_total{plane="python",direction="read"}`): the
    chunks of every `VolumeEcShardRead` stream, all holders, the HTTP
    bodies that the pooled front end writes through `wfile`, and what a
    shard plane without the native library sends."""
    from seaweedfs_tpu.utils import metrics

    return int(metrics.net_bytes_sent_total.snapshot().get(("python", "read"), 0))


def _shard_planes_sent(st: State) -> tuple[int, int]:
    """(native, Python) bytes that the live servers' shard planes
    (`VolumeServer.net_plane`, `ec/net_plane.ShardNetPlane`) have sent:
    `sendfile_bytes` and `python_bytes` of each. Shard ranges and
    nothing else in this cell: no HTTP body passes there, and no
    gateway asks a plane for a needle. A program or a server without a
    plane reads 0. NOT `sw_net_bytes_sent_total{plane="native"}`, which
    also books every large GET body (`utils/http_pool`)."""
    planes = [getattr(st.cluster.servers[s], "net_plane", None) for s in st.live]
    return (
        sum(int(getattr(p, "sendfile_bytes", 0)) for p in planes),
        sum(int(getattr(p, "python_bytes", 0)) for p in planes),
    )


class ServedMeter:
    """What the holders sent for shard ranges from its making to
    `read()`, whichever plane carried them: the `VolumeEcShardRead`
    streams (the Python plane's read bytes less the window's small HTTP
    bodies and less the shard planes' own Python egress, which that
    counter holds too) and the shard planes (native and Python egress
    together)."""

    def __init__(self, st: State):
        self.st = st
        self.python0 = _python_plane_sent()
        self.planes0 = _shard_planes_sent(st)

    def read(self, http_python_bytes: int) -> dict[str, int]:
        """`http_python_bytes`: the bodies of the window's GETs that
        left through `wfile` and so lie in the Python plane's counter."""
        native, python = _shard_planes_sent(self.st)
        native, python = native - self.planes0[0], python - self.planes0[1]
        stream = _python_plane_sent() - self.python0 - http_python_bytes - python
        return {
            "peer_bytes_served_stream": stream,
            "peer_bytes_served_plane": native + python,
            "peer_bytes_served": stream + native + python,
        }


def served_compared(counters: dict) -> list[Compared]:
    """The holders' bytes, by plane and summed (shown, held to
    nothing), and what `correct` rests on: SOME peer served a byte."""
    return [
        Compared(name, counters[name], None)
        for name in ("peer_bytes_served_stream", "peer_bytes_served_plane", "peer_bytes_served")
    ] + [Compared("no_peer_read", int(counters["peer_bytes_served"] <= 0), 0)]


def _peer_reader_bytes() -> int | None:
    """Bytes that peers answered EC reads with, counted at the READERS
    (`sw_ec_peer_read_bytes_total`, both kinds); None on a program that
    has no such counter."""
    from seaweedfs_tpu.utils import metrics

    counter = getattr(metrics, "ec_peer_read_bytes_total", None)
    return None if counter is None else int(sum(counter.snapshot().values()))


def _native_body_min() -> float:
    """Bodies under this length leave through `wfile` and are counted
    with the peers' streams (`utils/http_pool._send_parts`); every
    body, where the native plane is not there."""
    from seaweedfs_tpu.utils import http_pool

    return float("inf") if http_pool._native_mod() is None else http_pool._NATIVE_BODY_MIN


def window(cell, st: State, slice_) -> Observed:
    clients = int(cell.traffic["clients"])
    obs = Observed()
    lock = threading.Lock()
    wrong: list[str] = []
    failed: list[str] = []
    gets: list[tuple[float, float, bool, int]] = []  # (t0, t1, on a lost shard, server)
    hits0, misses0 = _cache_counts(st)
    rec0 = _reconstructed(st)
    served = ServedMeter(st)
    read0 = _peer_reader_bytes()
    http_python_bytes = [0] * clients  # bodies counted on the streams' plane
    native_min = _native_body_min()
    t_begin = time.perf_counter()
    deadline = t_begin + cell.seconds
    stop_ticks = threading.Event()

    def ticks() -> None:
        while not stop_ticks.wait(0.05):
            slice_.boundary()

    def work(w: int) -> None:
        conns = _connect(st)
        mine: list[tuple[float, float, bool, int]] = []
        keys = Y.needle_stream(cell, w, len(st.targets))
        entries = entry_draws(cell.seed, w, len(st.live))
        try:
            while True:
                i = next(keys)
                s = st.live[next(entries)]
                fid = st.volume.fid(i)
                t0 = time.perf_counter()
                if t0 >= deadline:
                    break
                try:
                    with annotate("ecbench.get"):
                        status, body = G._get(conns[s], fid)
                    t1 = time.perf_counter()
                except (OSError, http.client.HTTPException) as e:
                    failed.append(f"GET {fid} at server {s}: {type(e).__name__}: {e}")
                    conns[s].close()
                    conns[s] = http.client.HTTPConnection(
                        *st.cluster.host(s), timeout=G.GET_TIMEOUT_S
                    )
                    continue
                if status != 200 or body != st.volume.body(i):
                    wrong.append(f"GET {fid} at server {s} -> {status}, {len(body)} bytes")
                mine.append((t0, t1, i in st.on_lost, s))
                if status == 200 and len(body) < native_min:
                    http_python_bytes[w] += len(body)
        finally:
            for c in conns.values():
                c.close()
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
    obs.gets = [(t0, t1) for t0, t1, _lost, _s in gets]
    if not obs.gets:
        raise C.BenchError(f"no GET came back; first failure: {(failed or wrong or ['none'])[0]}")
    hits1, misses1 = _cache_counts(st)
    obs.t_start = t_begin
    obs.t_end = max(t1 for _t0, t1 in obs.gets)
    obs.attempted = len(obs.gets) + len(failed)
    obs.failed = len(failed) + len(wrong)
    obs.bytes = 0
    good = len(obs.gets) - len(wrong)
    obs.end_to_end["fg_p50_ms"], obs.end_to_end["fg_p95_ms"] = Y._p50_p95(
        [(t1 - t0) * 1e3 for t0, t1 in obs.gets]
    )
    obs.end_to_end["fg_ops_per_s"] = good / (obs.t_end - obs.t_start)
    has_cache = any(ev.interval_cache is not None for ev in st.evs)
    n_lost = sum(1 for _t0, _t1, lost, _s in gets if lost)
    by_server = {s: sum(1 for g in gets if g[3] == s) for s in st.live}
    obs.counters.update(
        cache_hits=hits1 - hits0, cache_misses=misses1 - misses0,
        bytes_reconstructed=_reconstructed(st) - rec0,
        gets_on_lost_shard=n_lost,
        # with no interval cache every GET on a lost shard reconstructs
        gets_reconstructing=misses1 - misses0 if has_cache else n_lost,
        entry_servers_unused=sum(1 for n in by_server.values() if n == 0),
        **served.read(sum(http_python_bytes)),
    )
    if read0 is not None:
        obs.counters["peer_reader_bytes"] = _peer_reader_bytes() - read0
    obs.notes.update(wrong=wrong, failed=failed, gets_by_server=by_server)
    print(
        "ecbench: GETs by entry server: "
        + " ".join(f"{s}={n}" for s, n in sorted(by_server.items())),
        file=sys.stderr, flush=True,
    )
    for name, want in (("off the lost shards", False), ("on a lost shard", True)):
        ms = [(t1 - t0) * 1e3 for t0, t1, lost, _s in gets if lost is want]
        if ms:
            p50, p95 = Y._p50_p95(ms)
            print(
                f"ecbench: GETs of needles {name}: {len(ms)}, p50 {p50:.2f} ms, p95 {p95:.2f} ms",
                file=sys.stderr, flush=True,
            )
    return obs


def verify(cell, st: State, obs: Observed, control: bool = False) -> list[Compared]:
    """`http_gets_ycsb`'s comparison, with caches, reconstructed bytes
    and faults summed over the servers, and what makes the cell the
    spread one: peers served bytes, every live server was entered, and
    no live server holds a shard beyond its own two."""
    compared = Y.verify(cell, st, obs, control=control)
    placed = cell.config["placement"]["shards_of_server"]
    if "peer_reader_bytes" in obs.counters:  # the readers' side of the same bytes
        compared.append(Compared("peer_reader_bytes", obs.counters["peer_reader_bytes"], None))
    return compared + served_compared(obs.counters) + [
        Compared("entry_servers_unused", obs.counters["entry_servers_unused"], 0),
        Compared(
            "shards_on_entry_server_only",
            st.cluster.shards_beyond_placement(st.volume.vid, placed), 0,
        ),
    ]


def teardown(st: State) -> None:
    st.cluster.stop()
