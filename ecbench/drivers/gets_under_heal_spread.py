"""A dead server's shards healed from its peers while the reads go on:
closed-loop YCSB-C GETs of volume 1 through the six live servers of the
seven (`http_gets_ycsb_spread`), while the shell's `ec.rebuild
-fromPeers` heals volume 2's lost pair, one heal after another. The
rebuilder (the smallest holder) streams its sibling rows from the other
live servers' shard planes, the same planes that answer the GETs' reads
from peers, rebuilds on the chip and hands the regenerated shards to
their planned holders.

Traffic parameters (traffic/<name>.json):
  clients, read_proportion, keys, request_distribution,
  zipfian_constant, popularity_seed, entry_server, warm_sweep,
  warm_draws, down_server
                          `http_gets_ycsb_spread`'s, unchanged
  get_volume              the volume the clients read (never healed)
  background_op           "ec.rebuild -fromPeers": the shell command, as typed
  background_volume       the volume it heals
  background_concurrency  1: one heal at a time, the next when the reset
                          after the last has returned
  heal_lost_shards        the dead server's shards of that volume: what
                          each heal regenerates
  background_from         "window_start": both classes start together

Set-up: both volumes are fabricated in server 0's directory, encoded by
the shell and spread by the RPCs of `ec.balance` as the spread cell
spreads its one; the dead server is stopped. Then the reads' warm-up of
`http_gets_ycsb_spread`, and one untimed heal.

The window: the GETs are `http_gets_ycsb_spread.window` (its clients,
its comparison, its three end-to-end numbers), which goes on until the
last heal has finished (`gets_under_rebuild.Until`). The heals run in a
thread beside it with `volume_ops`' rules: no heal starts after
`--seconds`, the running one finishes. Between two heals the pair is
kept under a second name where it landed (a hard link, no byte moves),
unmounted and deleted there, and the master is waited on until it lists
the pair nowhere. The driver reports no rate of its own: the heals'
pace is the per-layer `heal_mb_per_s`.

What is compared, beside the spread cell's comparison of the reads:
every pair a heal of the window regenerated, byte by byte, against the
reference encode of volume 2's .dat, and the `.ecsum` of each holder it
landed at; that after each heal every shard of the volume is mounted at
one live server and the master lists each of the pair at one
(`duplicate_holders`); that no source byte of a heal lay in the staging
directory (`heal_source_bytes_staged`: the largest the staging
directories held beside the targets, polled while each heal ran, and in
a traced run the `source_bytes_staged` of every `ec.peer_rebuild`
span); that peers sent sources (`heal_sources_from_peers`: the
rebuilder's count of the bytes its peers answered); that a heal lay
wholly inside the reads' window; and that the device queue admitted
GETs' reconstructions while a heal ran.
"""

from __future__ import annotations

import dataclasses
import os
import re
import sys
import threading
import time

import numpy as np

from ecbench import cluster as C
from ecbench import data as D
from ecbench import reference as R
from ecbench.harness import Compared, Observed, annotate, load_module
from ecbench.spread_cluster import SpreadCluster

S = load_module("drivers", "http_gets_ycsb_spread")
U = load_module("drivers", "gets_under_rebuild")
Y = S.Y
G = S.G

STAGING_PREFIX = ".peerfetch-"  # the program's staging directory, beside the volume
STAGING_POLL_S = 0.025
LISTING_TIMEOUT_S = 60.0


@dataclasses.dataclass
class Heals:
    """Volume 2 and what its heals left."""

    volume: D.SeededVolume
    lost: tuple[int, ...]
    src_base: str  # a second name of the volume's .dat (ec.encode drops it)
    keep_dir: str
    shard_bytes: int = 0
    kept: list[tuple[str, list[bytes]]] = dataclasses.field(default_factory=list)
    # (base of a regenerated pair kept under a second name, the .ecsum of
    # each server it landed at)
    duplicates: list[int] = dataclasses.field(default_factory=list)  # one a heal
    staged_max: int = 0
    rebuilders: list[str] = dataclasses.field(default_factory=list)  # url, one a heal
    rebuilder: int = -1  # the server the set-up's heal ran at
    commands: list[tuple[dict, dict]] = dataclasses.field(default_factory=list)
    # (queue counts before, after) of each heal's shell command


@dataclasses.dataclass
class State:
    cluster: SpreadCluster
    gets: object  # http_gets_ycsb_spread.State, volume `get_volume`
    heals: Heals


# ------------------------------------------------------------ the queues


def scopes(cl: SpreadCluster, servers=None) -> list:
    """The device-queue scopes of these servers (all live ones where
    none are named), each once."""
    seen: dict[int, object] = {}
    for i in cl.live() if servers is None else servers:
        scope = cl.servers[i].store.ec_scheduler
        seen.setdefault(id(scope), scope)
    return list(seen.values())


def queue_snapshot(cl: SpreadCluster, servers=None) -> list[tuple[int, dict]]:
    return [(id(sc), q) for sc in scopes(cl, servers) for q in sc.stats_snapshot()]


def queue_counts(cl: SpreadCluster) -> dict:
    """Batches admitted so far, by (queue, class)."""
    return {
        ((sid, q["backend"], q["chip"]), cls): int(c["admitted"])
        for sid, q in queue_snapshot(cl)
        for cls, c in q["classes"].items()
    }


def slot_seconds(cl: SpreadCluster, servers) -> dict | None:
    """Seconds of window slots held so far, by class, over the queues of
    these servers; None where the program keeps no such count."""
    total: dict[str, float] = {}
    for _sid, q in queue_snapshot(cl, servers):
        for cls, c in q["classes"].items():
            if "slot_s" not in c:
                return None
            total[cls] = total.get(cls, 0.0) + float(c["slot_s"])
    return total


def overlap(h: Heals) -> tuple[int, int]:
    """(foreground batches admitted while a heal of the window ran, how
    many queues admitted both classes meanwhile)."""
    pairs = 0
    both: set[tuple] = set()
    for before, after in h.commands:
        grew = {key: after[key] - before.get(key, 0) for key in after}
        pairs += sum(n for (_q, cls), n in grew.items() if cls == "foreground")
        both |= {
            q for (q, cls), n in grew.items()
            if cls == "recovery" and n > 0 and grew.get((q, "foreground"), 0) > 0
        }
    return pairs, len(both)


# ------------------------------------------------------------ the sources


def source_bytes() -> dict[str, int] | None:
    """The rebuilders' count of a heal's source bytes by where they came
    from (`sw_ec_peer_rebuild_source_bytes_total{plane}`); None on a
    program that keeps no such count."""
    from seaweedfs_tpu.utils import metrics

    counter = getattr(metrics, "ec_peer_rebuild_source_bytes_total", None)
    if counter is None:
        return None
    return {key[0]: int(v) for key, v in counter.snapshot().items()}


def staged_bytes(cl: SpreadCluster, vid: int, lost) -> int:
    """Bytes in the live servers' staging directories of volume `vid`
    that are not a target's: what a heal holds of its sources on disk."""
    outs = {f"{vid}{R.shard_ext(s)}{suffix}" for s in lost for suffix in ("", ".rebuilding")}
    total = 0
    for i in cl.live():
        sdir = os.path.join(cl.dirs[i], f"{STAGING_PREFIX}{vid}")
        try:
            with os.scandir(sdir) as it:
                for e in it:
                    if e.name not in outs and e.is_file(follow_symlinks=False):
                        total += e.stat(follow_symlinks=False).st_size
        except FileNotFoundError:
            continue
    return total


class StagingWatch:
    """While a heal runs, the most the staging directories held of its
    sources at any poll."""

    def __init__(self, cl: SpreadCluster, vid: int, lost):
        self.cl, self.vid, self.lost = cl, vid, lost
        self.most = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self) -> None:
        while True:
            self.most = max(self.most, staged_bytes(self.cl, self.vid, self.lost))
            if self._stop.wait(STAGING_POLL_S):
                return

    def __enter__(self) -> "StagingWatch":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()


# --------------------------------------------------------------- the heal


def holders_of(cl: SpreadCluster, vid: int) -> dict[int, list[int]]:
    """shard id -> the live servers that have it mounted."""
    out: dict[int, list[int]] = {}
    for i in cl.live():
        ev = cl.servers[i].store.find_ec_volume(vid)
        for sid in (ev.shard_ids if ev is not None else ()):
            out.setdefault(int(sid), []).append(i)
    return out


def _heal(st: State) -> tuple[float, float]:
    """One `ec.rebuild -fromPeers` of the volume, then its checks: where
    the pair landed, kept under a second name, and how many holders each
    shard has. Returns the command's (start, end)."""
    cl, h = st.cluster, st.heals
    vid = h.volume.vid
    before = queue_counts(cl)
    with StagingWatch(cl, vid, h.lost) as watch, annotate("ecbench.op.ec.rebuild"):
        t0 = time.perf_counter()
        out = cl.shell(f"ec.rebuild -volumeId {vid} -fromPeers")
        t1 = time.perf_counter()
    h.commands.append((before, queue_counts(cl)))
    h.staged_max = max(h.staged_max, watch.most)
    if f"rebuilt shards {list(h.lost)}" not in out:
        raise C.BenchError(f"ec.rebuild -fromPeers of {vid} -> {out}")
    found = re.search(r" on (\S+:\d+)", out)
    if found is None:
        raise C.BenchError(f"ec.rebuild -fromPeers of {vid} names no rebuilder: {out}")
    h.rebuilders.append(found.group(1))
    h.duplicates.append(_duplicates(st))
    _keep(st)
    return t0, t1


def _duplicates(st: State) -> int:
    """After a heal: shards of the volume mounted at more than one live
    server, and shards of the pair that the master does not list at
    exactly one (waited for until it lists each at one at least)."""
    cl, h = st.cluster, st.heals
    vid = h.volume.vid
    deadline = time.time() + LISTING_TIMEOUT_S
    while True:
        located = cl.located(vid)
        if all(located.get(s) for s in h.lost) or time.time() > deadline:
            break
        time.sleep(0.002)
    mounted = holders_of(cl, vid)
    extra = sum(max(0, len(at) - 1) for at in mounted.values())
    return extra + sum(1 for s in h.lost if len(located.get(s, ())) != 1)


def _keep(st: State) -> None:
    """A second name for the pair where it landed, and the `.ecsum` of
    every server it landed at, to be compared when the window has
    closed."""
    cl, h = st.cluster, st.heals
    vid = h.volume.vid
    base = os.path.join(h.keep_dir, f"{vid}_{len(h.kept)}")
    sidecars: list[bytes] = []
    at = holders_of(cl, vid)
    for sid in h.lost:
        for i in at.get(sid, ())[:1]:  # a shard with no holder is compared as missing
            here = os.path.join(cl.dirs[i], str(vid))
            os.link(here + R.shard_ext(sid), base + R.shard_ext(sid))
            with open(here + ".ecsum", "rb") as f:
                sidecars.append(f.read())
    h.kept.append((base, sidecars))


def _reset(st: State) -> None:
    """Take the pair away again wherever it lies (mounted or a hand-off
    copy left on disk), then wait until the master lists it nowhere."""
    from seaweedfs_tpu.pb import cluster_pb2 as pb

    cl, h = st.cluster, st.heals
    vid = h.volume.vid
    with annotate("ecbench.reset"):
        for i in cl.live():
            here = os.path.join(cl.dirs[i], str(vid))
            ids = [s for s in h.lost if os.path.exists(here + R.shard_ext(s))]
            if not ids:
                continue
            cl.stubs[i].VolumeEcShardsUnmount(
                pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=ids),
                timeout=C.SHELL_TIMEOUT_S,
            )
            cl.stubs[i].VolumeEcShardsDelete(
                pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=ids),
                timeout=C.SHELL_TIMEOUT_S,
            )
        deadline = time.time() + C.SHELL_TIMEOUT_S
        while any(cl.located(vid).get(s) for s in h.lost):
            if time.time() > deadline:
                raise C.BenchError(f"the master still lists shards {list(h.lost)} of {vid}")
            time.sleep(0.002)


# ----------------------------------------------------------------- set-up


def setup(cell) -> State:
    cfg, traffic = cell.config, cell.traffic
    if source_bytes() is None:
        # a program from before a heal's sources streamed to the chip:
        # it writes them to disk and counts nothing of them
        raise C.BenchError(
            "this program keeps no count of a heal's source bytes "
            "(sw_ec_peer_rebuild_source_bytes_total): the cell cannot tell where they came from"
        )
    if (traffic["background_op"], int(traffic["background_concurrency"])) != (
        "ec.rebuild -fromPeers", 1
    ):
        raise C.BenchError("gets_under_heal_spread runs one stream of ec.rebuild -fromPeers, nothing else")
    if traffic["background_from"] != "window_start":
        raise C.BenchError("gets_under_heal_spread starts both classes with the window")
    if (traffic["request_distribution"], traffic["keys"]) != ("zipfian", "all"):
        raise C.BenchError("the reads are http_gets_ycsb_spread's: zipfian ranks over all keys")
    if float(traffic["read_proportion"]) != 1.0:
        raise C.BenchError("the reads are http_gets_ycsb_spread's: reads only (YCSB workload C)")
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
    if tuple(int(s) for s in traffic["heal_lost_shards"]) != lost:
        raise C.BenchError(f"the heals regenerate {traffic['heal_lost_shards']}, server {down} held {lost}")
    get_vid, heal_vid = int(traffic["get_volume"]), int(traffic["background_volume"])
    n_vol = int(cfg["volumes"])
    if get_vid == heal_vid or not {get_vid, heal_vid} <= set(range(1, n_vol + 1)):
        raise C.BenchError(f"volumes {get_vid} and {heal_vid} of {n_vol}: two different ones")
    vol_dir = os.path.join(cell.data_dir, "vs0")
    src_dir, keep_dir = (os.path.join(cell.data_dir, d) for d in ("src", "kept"))
    for d in (vol_dir, src_dir, keep_dir):
        os.makedirs(d)
    volumes = {
        vid: D.fabricate_volume(vol_dir, vid, cell.seed, int(cfg["volume_bytes"]), cfg["needles"])
        for vid in range(1, n_vol + 1)
    }
    cell.mark("volumes")
    heal_vol = volumes[heal_vid]
    h = Heals(
        volume=heal_vol, lost=lost,
        src_base=os.path.join(src_dir, f"src{heal_vid}"), keep_dir=keep_dir,
    )
    # ec.encode drops the source volume: the reference encodes this .dat
    for ext in (".dat", ".idx"):
        os.link(heal_vol.base + ext, h.src_base + ext)
    cl = SpreadCluster(cell.data_dir, cfg, cell.traced, servers=len(placed))
    vol = volumes[get_vid]
    gets = S.State(cluster=cl, volume=vol, targets=list(range(len(vol.sizes))),
                   on_lost=frozenset(), live=[], evs=[])
    st = State(cluster=cl, gets=gets, heals=h)
    try:
        for vid in volumes:
            cl.wait_volume_listed(vid)
        cell.mark("cluster")
        for vid in volumes:
            cl.shell(f"ec.encode -volumeId {vid}")
        cell.mark("encoded")
        for vid in volumes:
            for dst in range(1, len(placed)):
                cl.move_shards(vid, 0, dst, placed[dst])
            cl.wait_placement(vid, placed)
        cell.mark("spread")
        cl.stop_server(down)
        for vid in volumes:
            cl.wait_placement(vid, placed)  # the dead server's shards: nowhere
        cell.mark("server_down")
        h.shard_bytes = os.path.getsize(os.path.join(cl.dirs[0], str(heal_vid)) + R.shard_ext(0))

        # the volume under reads, as http_gets_ycsb_spread.setup leaves its own
        gets.live = cl.live()
        gets.evs = [cl.servers[s].store.find_ec_volume(get_vid) for s in gets.live]
        if None in gets.evs:
            raise C.BenchError("a live server has not mounted the EC volume")
        on_lost = sorted({
            i for sid in lost if sid < k for i in G.needles_on_shard(vol, sid, layout)
        })
        if not on_lost or len(on_lost) == len(vol.sizes):
            raise C.BenchError(
                f"{len(on_lost)} of {len(vol.sizes)} needles have bytes on the lost data "
                f"shards of {lost}: the cell needs needles on them and needles off them"
            )
        gets.on_lost = frozenset(on_lost)
        S._sweep(gets, [(s, i) for i in on_lost for s in gets.live], clients)
        cell.mark(f"swept_{len(on_lost)}_on_lost_shards_through_{len(gets.live)}_servers")
        warm_keys = Y.needle_stream(cell, Y.WARM_CLIENT, len(gets.targets))
        warm_entry = S.entry_draws(cell.seed, Y.WARM_CLIENT, len(gets.live))
        S._sweep(
            gets,
            [(gets.live[next(warm_entry)], next(warm_keys))
             for _ in range(int(traffic["warm_draws"]))],
            clients,
        )
        cell.mark("warmed")

        # the volume under heal: one whole untimed heal (its shapes
        # compiled, the batch pool filled), then the pair taken away
        _heal(st)
        _reset(st)
        for base, _sidecars in h.kept:  # the warm-up's are not the window's
            for sid in lost:
                if os.path.exists(base + R.shard_ext(sid)):
                    os.unlink(base + R.shard_ext(sid))
        h.kept.clear()
        h.duplicates.clear()
        h.commands.clear()
        h.staged_max = 0
        h.rebuilder = next(
            i for i in gets.live if cl.host(i)[1] == int(h.rebuilders[0].rsplit(":", 1)[1])
        )
        h.rebuilders.clear()
        cell.mark("healed_once")
        cl.arm_tracer()
    except BaseException:
        cl.stop()
        raise
    os.sync()  # the set-up's write-back must not land in the window
    return st


# ----------------------------------------------------------------- window


def window(cell, st: State, slice_) -> Observed:
    cl, h = st.cluster, st.heals
    done = threading.Event()
    background: dict = {"ops": []}
    t_begin = time.perf_counter()
    deadline = t_begin + cell.seconds
    sources0 = source_bytes()
    # the queue the heals run through, which also serves the GETs that
    # enter at the rebuilder: what `recovery_slot_share` reads
    slots0 = slot_seconds(cl, [h.rebuilder])

    def heals() -> None:
        ops = background["ops"]
        try:
            while True:
                t0, t1 = _heal(st)
                ops.append(("op", h.volume.vid, t0, t1, int(cell.config["layout"]["data_shards"]) * h.shard_bytes))
                if t1 >= deadline:
                    return  # the last heal's pair stays where it landed
                _reset(st)
                ops.append(("reset", h.volume.vid, t1, time.perf_counter(), 0))
        except BaseException as e:  # noqa: BLE001 - raised below, on the harness's thread
            background["error"] = e
        finally:
            done.set()

    thread = threading.Thread(target=heals, daemon=True)
    thread.start()
    obs = S.window(dataclasses.replace(cell, seconds=U.Until(cell.seconds, done)), st.gets, slice_)
    thread.join()
    if "error" in background:
        raise background["error"]
    ops = background["ops"]
    done_ops = [o for o in ops if o[0] == "op"]
    obs.ops = ops
    obs.bytes = sum(o[4] for o in done_ops)
    obs.attempted += len(done_ops)
    sources1 = source_bytes()
    grew = {p: sources1.get(p, 0) - sources0.get(p, 0) for p in sources1}
    # the reads' window: it opens with the first heal and closes on the
    # last GET, which the clients send until the last heal has finished
    inside = sum(1 for o in done_ops if o[2] >= obs.t_start and o[3] <= obs.t_end)
    obs.counters.update(
        heals=len(done_ops), heals_inside_window=inside,
        heal_source_bytes=grew,
        queue_window=sum(int(q["window"]) for _sid, q in queue_snapshot(cl, [h.rebuilder])),
    )
    slots1 = slot_seconds(cl, [h.rebuilder])
    if slots0 is not None and slots1 is not None:
        obs.counters["queue_slot_seconds"] = {
            cls: slots1[cls] - slots0.get(cls, 0.0) for cls in slots1
        }
    print(
        "ecbench: heals (start s, seconds, rebuilder): "
        + " ".join(
            f"{o[2] - t_begin:.2f}+{o[3] - o[2]:.3f}@{who}"
            for o, who in zip(done_ops, h.rebuilders)
        )
        + "; resets (seconds): "
        + " ".join(f"{o[3] - o[2]:.3f}" for o in ops if o[0] == "reset")
        + "; source bytes by plane: "
        + " ".join(f"{p}={n}" for p, n in sorted(grew.items())),
        file=sys.stderr, flush=True,
    )
    return obs


# ----------------------------------------------------------------- verify


def _spans_staged(obs: Observed) -> int:
    """`source_bytes_staged` summed over the window's `ec.peer_rebuild`
    spans (a traced run; 0 where there are none)."""
    from ecbench.layerlib import walk

    return sum(
        int(d["attrs"].get("source_bytes_staged", 0))
        for root in obs.spans for d in walk(root) if d["op"] == "ec.peer_rebuild"
    )


def verify(cell, st: State, obs: Observed, control: bool = False) -> list[Compared]:
    """`http_gets_ycsb_spread`'s comparison of the reads, and the heals'
    (see the top of the file). With `control` the reference leaves one
    product out of one parity row (every holder's `.ecsum` then records
    other CRCs than the reference's) and every key is answered with its
    neighbour's body: the comparison has to fail."""
    h = st.heals
    layout = cell.config["layout"]
    k = int(layout["data_shards"])
    compared = S.verify(cell, st.gets, obs, control=control)
    row = next((s - k for s in h.lost if s >= k), 2)
    dat = np.memmap(h.src_base + ".dat", dtype=np.uint8, mode="r")
    want = R.encode(dat, layout, drop_term=(row, 7) if control else None)
    differing = sidecar_faults = 0
    for base, sidecars in h.kept:
        differing += R.compare_shards(base, want, h.lost)
        sidecar_faults += sum(R.compare_sidecar(raw, want) for raw in sidecars)
        sidecar_faults += 4 * max(0, len(h.lost) - len(sidecars))  # a shard nowhere
    del want, dat
    heals = obs.counters["heals"]
    from_peers = sum(n for p, n in obs.counters["heal_source_bytes"].items() if p != "local")
    staged = max(h.staged_max, _spans_staged(obs))
    pairs, queues = overlap(h)
    compared += [
        Compared("heals", heals, None),
        Compared("heal_sets_compared_byte_by_byte", len(h.kept), None),
        Compared("heal_shard_files_differing", differing, 0),
        Compared("heal_ecsum_fields_differing", sidecar_faults, 0),
        Compared("heal_sets_not_compared", heals - len(h.kept), 0),
        Compared("duplicate_holders", sum(h.duplicates), 0),
        Compared("heal_source_bytes_staged", staged, 0),
        Compared("heal_sources_from_peers", from_peers, None),
        Compared("no_heal_source_from_peers", int(from_peers <= 0), 0),
        Compared("heals_inside_window", obs.counters["heals_inside_window"], None),
        Compared("no_heal_in_window", int(obs.counters["heals_inside_window"] <= 0), 0),
        Compared("overlapped_pairs", pairs, None),
        Compared("no_overlapped_pair", int(pairs <= 0), 0),
        Compared("classes_never_in_one_queue", int(queues < 1), 0),
    ]
    once: dict[str, Compared] = {}
    for c in compared:
        once.setdefault(c.name, c)
    return list(once.values())


def teardown(st: State) -> None:
    st.cluster.stop()
