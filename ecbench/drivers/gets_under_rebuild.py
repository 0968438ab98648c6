"""Two classes of device work in one window: closed-loop YCSB-C GETs on
one volume that has lost shards (`http_gets_ycsb`) while the same
shards of a second volume are rebuilt through the shell, back to back
(`volume_ops`). One master, one volume server, one store, one device
queue: foreground reconstructions and recovery batches meet there.

Traffic parameters (traffic/<name>.json):
  clients, read_proportion, keys, request_distribution,
  zipfian_constant, popularity_seed, warm_draws
                          `http_gets_ycsb`'s, unchanged
  get_volume              the volume the clients read (never rebuilt)
  get_lost_shards         its shards unlinked and unmounted in set-up
  background_op           "ec.rebuild": the shell command, as typed
  background_volume       the volume it runs on
  background_concurrency  1: one stream, the next operation starts when
                          the reset after the last has returned
  rebuild_lost_shards     taken away before each operation, the rebuilt
                          pair kept under a second name (`volume_ops`)
  background_from         "window_start": both classes start together

Nothing is computed here that a driver that is there computes: the GETs
are `http_gets_ycsb.window` (its clients, streams, comparison and its
three end-to-end numbers), the rebuilds `volume_ops.window` (its loop,
its reset, its rule that no operation starts after `seconds` and the
running one finishes, its MB/s) in a thread beside it. The one thing
added is when the clients stop: not at `seconds` but when the last
rebuild has finished too, so that no operation of the window runs alone
(`Until`). `verify` is both drivers' comparisons, and that the two
classes did overlap and went through one queue.
"""

from __future__ import annotations

import dataclasses
import os
import threading

from ecbench import cluster as C
from ecbench import data as D
from ecbench import reference as R
from ecbench.harness import Compared, Observed, load_module

Y = load_module("drivers", "http_gets_ycsb")
V = load_module("drivers", "volume_ops")
G = Y.G


class Until:
    """Stands where `cell.seconds` stands in `http_gets_ycsb.window`:
    `t_begin + Until(...)` is a deadline that a client's clock has
    passed (`t0 >= deadline`) once `seconds` are over AND `done` is
    set."""

    def __init__(self, seconds: float, done: threading.Event):
        self.seconds, self.done, self.at = float(seconds), done, None

    def __radd__(self, t_begin: float) -> "Until":
        self.at = t_begin + self.seconds
        return self

    def __le__(self, now: float) -> bool:  # now >= deadline
        return now >= self.at and self.done.is_set()


class NoSlice:
    """`volume_ops.window`'s slice: the clients' ticker moves the real
    one, and a rebuild that ends must not close it."""

    def boundary(self) -> None:
        pass

    close = boundary


class WatchedCluster:
    """The cluster as `volume_ops` sees it. Around every shell command
    it notes what the server's device queues had admitted by class:
    what the other class was given while the command ran."""

    def __init__(self, cluster: C.Cluster):
        self._cluster = cluster
        self.commands: list[tuple[str, dict, dict]] = []  # (line, before, after)

    def __getattr__(self, name):
        return getattr(self._cluster, name)

    def shell(self, line: str) -> str:
        before = queue_counts(self._cluster)
        out = self._cluster.shell(line)
        self.commands.append((line, before, queue_counts(self._cluster)))
        return out


@dataclasses.dataclass
class State:
    cluster: C.Cluster
    gets: object  # http_gets_ycsb.State, volume `get_volume`
    ops: object  # volume_ops.State, volume `background_volume`
    ops_traffic: dict  # the traffic file volume_ops would have been given


def queue_snapshot(cluster: C.Cluster) -> list[dict]:
    return cluster.vs.store.ec_scheduler.stats_snapshot()


def queue_counts(cluster: C.Cluster) -> dict:
    """Batches admitted so far, by (queue, class)."""
    return {
        ((q["backend"], q["chip"]), cls): int(c["admitted"])
        for q in queue_snapshot(cluster)
        for cls, c in q["classes"].items()
    }


def slot_seconds(cluster: C.Cluster) -> dict | None:
    """Seconds of window slots held so far, by class, over the server's
    queues; None where the program keeps no such count."""
    total: dict[str, float] = {}
    for q in queue_snapshot(cluster):
        for cls, c in q["classes"].items():
            if "slot_s" not in c:
                return None
            total[cls] = total.get(cls, 0.0) + float(c["slot_s"])
    return total


def setup(cell) -> State:
    cfg, traffic = cell.config, cell.traffic
    if (traffic["background_op"], int(traffic["background_concurrency"])) != ("ec.rebuild", 1):
        raise C.BenchError("gets_under_rebuild runs one stream of ec.rebuild, nothing else")
    if traffic["background_from"] != "window_start":
        raise C.BenchError("gets_under_rebuild starts both classes with the window")
    if (traffic["request_distribution"], traffic["keys"]) != ("zipfian", "all"):
        raise C.BenchError("the reads are http_gets_ycsb's: zipfian ranks over all keys")
    if float(traffic["read_proportion"]) != 1.0:
        raise C.BenchError("the reads are http_gets_ycsb's: reads only (YCSB workload C)")
    layout = cfg["layout"]
    k, m = int(layout["data_shards"]), int(layout["parity_shards"])
    get_vid, op_vid = int(traffic["get_volume"]), int(traffic["background_volume"])
    n_vol = int(cfg["volumes"])
    if get_vid == op_vid or not {get_vid, op_vid} <= set(range(1, n_vol + 1)):
        raise C.BenchError(f"volumes {get_vid} and {op_vid} of {n_vol}: two different ones")
    clients = int(traffic["clients"])
    src_dir, vol_dir, keep_dir = (
        os.path.join(cell.data_dir, d) for d in ("src", "vol", "kept")
    )
    for d in (src_dir, vol_dir, keep_dir):
        os.makedirs(d)
    # the run's seed fills the bodies, a different stream per volume id
    volumes = {
        vid: D.fabricate_volume(vol_dir, vid, cell.seed, int(cfg["volume_bytes"]), cfg["needles"])
        for vid in range(1, n_vol + 1)
    }
    cell.mark("volumes")
    cl = C.Cluster(vol_dir, cfg, cell.traced, max_volumes=n_vol + 8)
    cell.mark("cluster")
    ops_traffic = {
        "driver": "volume_ops", "op": "ec.rebuild", "concurrency": 1,
        "lost_shards": list(traffic["rebuild_lost_shards"]),
    }
    op_vol = volumes[op_vid]
    ops = V.State(
        cluster=WatchedCluster(cl), volumes=[op_vol], src_dir=src_dir, op="ec.rebuild",
        lost=tuple(int(s) for s in ops_traffic["lost_shards"]),
        total_shards=k + m, op_bytes={}, keep_dir=keep_dir,
    )
    # ec.encode drops the source volume: the reference encodes this .dat
    for ext in (".dat", ".idx"):
        os.link(op_vol.base + ext, V._source_base(ops, op_vid) + ext)
    for vid in volumes:
        cl.wait_volume_listed(vid)
        cl.shell(f"ec.encode -volumeId {vid}")
    cell.mark("encoded")

    # the volume under reads, as http_gets_ycsb.setup leaves its own
    vol = volumes[get_vid]
    lost = tuple(int(s) for s in traffic["get_lost_shards"])
    ev = cl.vs.store.find_ec_volume(get_vid)
    if ev is None:
        raise C.BenchError("the EC volume is not mounted after ec.encode")
    for sid in lost:  # file first (still mounted and advertised), then unmount
        os.unlink(vol.base + R.shard_ext(sid))
    cl.unmount_shards(get_vid, lost)
    on_lost = sorted({
        i for sid in lost if sid < k for i in G.needles_on_shard(vol, sid, layout)
    })
    if not on_lost or len(on_lost) == len(vol.sizes):
        raise C.BenchError(
            f"{len(on_lost)} of {len(vol.sizes)} needles have bytes on the lost data "
            f"shards of {lost}: the cell needs needles on them and needles off them"
        )
    gets = Y.State(
        cluster=cl, volume=vol, targets=list(range(len(vol.sizes))), ev=ev,
        on_lost=frozenset(on_lost),
    )

    # the volume under rebuild, as volume_ops.setup leaves its own: one
    # whole untimed operation (its shapes compiled, the batch pool
    # filled, the window's operations writing where this one's output lay)
    ops.op_bytes[op_vid] = os.path.getsize(op_vol.base + R.shard_ext(0)) * k
    V._reset(ops, op_vol)
    V._do(ops, op_vid)
    V._reset(ops, op_vol)
    for _vid, base in ops.kept:  # the warm-up's are not the window's
        for sid in ops.lost:
            os.unlink(base + R.shard_ext(sid))
    ops.kept.clear()
    cell.mark("rebuilt_once")

    # the reads' warm-up: every extent width the window can meet
    # compiles here, and the window opens on the cache's steady state
    Y._sweep(gets, on_lost, clients)
    cell.mark(f"swept_{len(on_lost)}_on_lost_shards_of_{len(gets.targets)}")
    warm = Y.needle_stream(cell, Y.WARM_CLIENT, len(gets.targets))
    Y._sweep(gets, [next(warm) for _ in range(int(traffic["warm_draws"]))], clients)
    cell.mark("warmed")
    os.sync()  # the set-up's write-back must not land in the window
    return State(cluster=cl, gets=gets, ops=ops, ops_traffic=ops_traffic)


def ops_cell(cell, st: State):
    """The cell as `volume_ops` would have been handed it."""
    return dataclasses.replace(cell, traffic=st.ops_traffic)


def window(cell, st: State, slice_) -> Observed:
    done = threading.Event()
    background: dict = {}

    def rebuilds() -> None:
        try:
            background["obs"] = V.window(ops_cell(cell, st), st.ops, NoSlice())
        except BaseException as e:  # noqa: BLE001 - raised below, on the harness's thread
            background["error"] = e
        finally:
            done.set()

    st.ops.cluster.commands.clear()
    slots0 = slot_seconds(st.cluster)
    thread = threading.Thread(target=rebuilds, daemon=True)
    thread.start()
    obs = Y.window(dataclasses.replace(cell, seconds=Until(cell.seconds, done)), st.gets, slice_)
    thread.join()
    if "error" in background:
        raise background["error"]
    ops = background["obs"]
    obs.ops, obs.bytes = ops.ops, ops.bytes
    obs.attempted += ops.attempted
    obs.end_to_end.update(ops.end_to_end)
    obs.notes["volume_ops"] = ops
    obs.counters["queue_window"] = sum(int(q["window"]) for q in queue_snapshot(st.cluster))
    slots1 = slot_seconds(st.cluster)
    if slots0 is not None and slots1 is not None:
        obs.counters["queue_slot_seconds"] = {
            cls: slots1[cls] - slots0.get(cls, 0.0) for cls in slots1
        }
    return obs


def overlap(st: State) -> tuple[int, int]:
    """(foreground batches the server's queues admitted while a rebuild
    of the window ran: each a reconstruction that met a rebuild, how
    many queues admitted both classes meanwhile)."""
    pairs = 0
    both: set[tuple] = set()
    for line, before, after in st.ops.cluster.commands:
        if not line.startswith("ec.rebuild"):
            continue
        grew = {key: after[key] - before.get(key, 0) for key in after}
        pairs += sum(n for (_q, cls), n in grew.items() if cls == "foreground")
        both |= {
            q for (q, cls), n in grew.items()
            if cls == "recovery" and n > 0 and grew.get((q, "foreground"), 0) > 0
        }
    return pairs, len(both)


def verify(cell, st: State, obs: Observed, control: bool = False) -> list[Compared]:
    """`http_gets_ycsb`'s comparison of every GET and `volume_ops`' of
    every rebuilt shard and sidecar, each with its own control (every
    key answered with its neighbour's body; a product left out of a
    parity row), and that the window was what the cell is for: some
    reconstruction was admitted while a rebuild ran, by the queue that
    admitted the rebuild's batches."""
    compared = Y.verify(cell, st.gets, obs, control=control)
    compared += V.verify(ops_cell(cell, st), st.ops, obs.notes["volume_ops"], control=control)
    pairs, queues = overlap(st)
    compared += [
        Compared("rebuilds", obs.notes["volume_ops"].attempted, None),
        Compared("overlapped_pairs", pairs, None),
        Compared("no_overlapped_pair", int(pairs <= 0), 0),
        Compared("classes_not_in_one_queue", int(queues != 1), 0),
    ]
    # both drivers hold the one backend to the same two limits: once each
    once: dict[str, Compared] = {}
    for c in compared:
        once.setdefault(c.name, c)
    return list(once.values())


def teardown(st: State) -> None:
    st.cluster.stop()
