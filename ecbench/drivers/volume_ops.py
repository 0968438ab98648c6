"""Whole-volume operations through the shell, one after another or
several side by side: `ec.encode` and `ec.rebuild`.

Traffic parameters (traffic/<name>.json):
  op           "ec.encode" | "ec.rebuild"
  concurrency  workers; worker w takes volumes w, w+concurrency, ...
  lost_shards  for ec.rebuild: the shards taken away before each run

An operation is the shell command as an operator types it. Between two
operations on a volume the benchmark puts the volume back: for
ec.encode it unmounts and deletes the shards, links .dat/.idx back and
mounts the volume; for ec.rebuild it unlinks and unmounts the lost
shards. The window stops on whole operations: an operation that ends
after `seconds` is a worker's last (so no turn, a reset and then its
operation, starts after `seconds`), and the clock stops with the last
of them. Worker w's volumes are w, w+concurrency, ...: with as many
workers as volumes every volume's last output lies in place then.

What is compared, once the window has closed, against the reference
encode of the same .dat:
  ec.rebuild  every shard file that every operation of the window
      rebuilt, byte by byte: the reset keeps each pair under a second
      name (a hard link, no byte moves) before the RPC unlinks it.
  ec.encode   of every operation the `.ecsum` it published (the CRCs the
      program took of the bytes it wrote, in the pass that wrote them),
      and of each volume's last operation every shard file, byte by
      byte, where it lies. Keeping 1.4 GiB an operation under a second
      name was tried and dropped: the next operation then writes to
      fresh blocks, 2.0-2.5 s where a steady one takes 1.37 (my chip
      run, PR 24).
"""

from __future__ import annotations

import dataclasses
import os
import sys
import threading
import time

import numpy as np

from ecbench import cluster as C
from ecbench import data as D
from ecbench import reference as R
from ecbench.harness import Compared, Observed, annotate


@dataclasses.dataclass
class State:
    cluster: C.Cluster
    volumes: list[D.SeededVolume]
    src_dir: str
    op: str
    lost: tuple[int, ...]
    total_shards: int
    op_bytes: dict[int, int]
    keep_dir: str = ""
    sidecars: list[tuple[int, bytes]] = dataclasses.field(default_factory=list)
    # (vid, the .ecsum an operation published); guarded by `lock`
    kept: list[tuple[int, str]] = dataclasses.field(default_factory=list)
    # (vid, base of a rebuilt set kept under a second name); guarded by `lock`
    lock: threading.Lock = dataclasses.field(default_factory=threading.Lock)


def _source_base(st: State, vid: int) -> str:
    return os.path.join(st.src_dir, f"src{vid}")


def _do(st: State, vid: int, op: str | None = None) -> None:
    op = op or st.op
    with annotate(f"ecbench.op.{op}"):
        out = st.cluster.shell(f"{op} -volumeId {vid}")
    if op == "ec.rebuild" and f"rebuilt shards {list(st.lost)}" not in out:
        raise C.BenchError(f"ec.rebuild of {vid} -> {out}")


def _note_sidecar(st: State, vol: D.SeededVolume) -> None:
    with open(vol.base + ".ecsum", "rb") as f:
        raw = f.read()
    with st.lock:
        st.sidecars.append((vol.vid, raw))


def _keep_rebuilt(st: State, vol: D.SeededVolume) -> None:
    """A second name for the shards an ec.rebuild just wrote, so that
    they outlive the reset and are compared when the window has closed."""
    with st.lock:
        base = os.path.join(st.keep_dir, f"{vol.vid}_{len(st.kept)}")
        st.kept.append((vol.vid, base))
    for sid in st.lost:
        os.link(vol.base + R.shard_ext(sid), base + R.shard_ext(sid))


def _reset(st: State, vol: D.SeededVolume) -> None:
    """Make the volume ready for the next operation."""
    with annotate("ecbench.reset"):
        c, vid = st.cluster, vol.vid
        every = range(st.total_shards)
        if st.op == "ec.encode":
            c.unmount_shards(vid, every)
            c.delete_shards(vid, every)
            if os.path.exists(vol.base + ".vif"):
                os.unlink(vol.base + ".vif")
            for ext in (".dat", ".idx"):
                os.link(_source_base(st, vid) + ext, vol.base + ext)
            c.mount_volume(vid)
            c.wait_volume_listed(vid)
        else:
            _keep_rebuilt(st, vol)
            # unmount, then the RPC that unlinks the files: it sends the
            # master a heartbeat, which then shows the shards gone
            c.unmount_shards(vid, st.lost)
            c.delete_shards(vid, st.lost)
            c.wait_shards_dropped(vid, st.lost)


def setup(cell) -> State:
    cfg, traffic = cell.config, cell.traffic
    op = traffic["op"]
    if op not in ("ec.encode", "ec.rebuild"):
        raise C.BenchError(f"volume_ops knows ec.encode and ec.rebuild, not {op!r}")
    layout = cfg["layout"]
    k, m = int(layout["data_shards"]), int(layout["parity_shards"])
    n_vol = int(cfg["volumes"])
    workers = int(traffic["concurrency"])
    if not 1 <= workers <= n_vol:
        raise C.BenchError(f"{workers} workers over {n_vol} volumes")
    src_dir = os.path.join(cell.data_dir, "src")
    vol_dir = os.path.join(cell.data_dir, "vol")
    keep_dir = os.path.join(cell.data_dir, "kept")
    for d in (src_dir, vol_dir, keep_dir):
        os.makedirs(d)
    volumes = [
        D.fabricate_volume(vol_dir, vid, cell.seed, int(cfg["volume_bytes"]), cfg["needles"])
        for vid in range(1, n_vol + 1)
    ]
    cell.mark("volumes")
    cl = C.Cluster(vol_dir, cfg, cell.traced, max_volumes=n_vol + 8)
    cell.mark("cluster")
    st = State(
        cluster=cl, volumes=volumes, src_dir=src_dir, op=op,
        lost=tuple(int(s) for s in traffic.get("lost_shards", ())),
        total_shards=k + m, op_bytes={}, keep_dir=keep_dir,
    )
    for vol in volumes:
        # ec.encode drops the source volume: keep its inodes
        for ext in (".dat", ".idx"):
            os.link(vol.base + ext, _source_base(st, vol.vid) + ext)
        # what one operation turns over: the .dat for an encode, the k
        # shards read for a rebuild
        st.op_bytes[vol.vid] = vol.dat_bytes
        cl.wait_volume_listed(vol.vid)
    if workers > 1:
        cl.hold_admin_lease()
    # one whole untimed operation on every volume: every program is
    # compiled, and the operations of the window write where this one's
    # output lay (a first operation on fresh blocks is half as fast)
    for vol in volumes:
        if op == "ec.rebuild":
            _do(st, vol.vid, "ec.encode")
            shard = os.path.getsize(vol.base + R.shard_ext(0))
            st.op_bytes[vol.vid] = shard * k
            _reset(st, vol)
        _do(st, vol.vid)
        _reset(st, vol)
    for _vid, base in st.kept:  # the warm-up's are not the window's
        for sid in st.lost:
            os.unlink(base + R.shard_ext(sid))
    st.kept.clear()
    cell.mark("warm")
    os.sync()  # the set-up's write-back must not land in the window
    return st


def window(cell, st: State, slice_) -> Observed:
    traffic = cell.traffic
    workers = int(traffic["concurrency"])
    obs = Observed()
    lock = threading.Lock()
    errors: list[BaseException] = []
    in_place: set[int] = set()
    t_begin = time.perf_counter()
    deadline = t_begin + cell.seconds

    def work(w: int) -> None:
        mine = st.volumes[w::workers]
        n = 0
        try:
            while True:
                vol = mine[n % len(mine)]
                slice_.boundary()
                t0 = time.perf_counter()
                _do(st, vol.vid)
                t1 = time.perf_counter()
                _note_sidecar(st, vol)
                with lock:
                    obs.ops.append(("op", vol.vid, t0, t1, st.op_bytes[vol.vid]))
                if t1 >= deadline:
                    with lock:
                        in_place.add(vol.vid)  # compared where it lies
                    return
                _reset(st, vol)
                with lock:
                    obs.ops.append(("reset", vol.vid, t1, time.perf_counter(), 0))
                n += 1
        except BaseException as e:  # noqa: BLE001 - reported by the harness
            errors.append(e)

    threads = [threading.Thread(target=work, args=(w,), daemon=True) for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    slice_.close()
    if errors:
        raise errors[0]
    done = [o for o in obs.ops if o[0] == "op"]
    obs.t_start = min(o[2] for o in done)
    obs.t_end = max(o[3] for o in done)
    obs.attempted = len(done)
    obs.failed = 0
    obs.bytes = sum(o[4] for o in done)
    obs.end_to_end["volume_mb_per_s"] = obs.bytes / 1e6 / (obs.t_end - obs.t_start)
    obs.notes["in_place"] = in_place
    print(
        "ecbench: operations (kind, volume, start s, seconds): "
        + " ".join(
            f"{kind}:{vid}@{t0 - t_begin:.2f}+{t1 - t0:.3f}"
            for kind, vid, t0, t1, _b in sorted(obs.ops, key=lambda o: o[2])
        ),
        file=sys.stderr, flush=True,
    )
    return obs


def _reference(cell, st: State, vol: D.SeededVolume, broken: bool) -> R.Encoded:
    """With `broken`, one product is left out of one parity row: a row
    that the operation writes (for a rebuild, the first lost parity
    shard's)."""
    k = int(cell.config["layout"]["data_shards"])
    row = next((s - k for s in st.lost if s >= k), 2)
    dat = np.memmap(_source_base(st, vol.vid) + ".dat", dtype=np.uint8, mode="r")
    return R.encode(dat, cell.config["layout"], drop_term=(row, 7) if broken else None)


def verify(cell, st: State, obs: Observed, control: bool = False) -> list[Compared]:
    """Against the reference encode of the same .dat (see the top of the
    file for what is compared). With `control` the reference leaves one
    product out of one parity row and stands in the program's place: the
    comparison has to fail."""
    layout = cell.config["layout"]
    k, m = int(layout["data_shards"]), int(layout["parity_shards"])
    ids = None if st.op == "ec.encode" else st.lost
    sets = differing = sidecars = sidecar_faults = 0
    for vol in st.volumes:
        want = _reference(cell, st, vol, control)
        bases = [base for vid, base in st.kept if vid == vol.vid]
        if vol.vid in obs.notes["in_place"]:
            bases.append(vol.base)
        for base in bases:
            sets += 1
            differing += R.compare_shards(base, want, ids)
        for vid, raw in st.sidecars:
            if vid == vol.vid:
                sidecars += 1
                sidecar_faults += R.compare_sidecar(raw, want)
        del want
    # an encode's earlier outputs are compared by their sidecars alone
    whole = obs.attempted if st.op == "ec.rebuild" else len(st.volumes)
    not_device, fallen = st.cluster.backend_faults(k, m)
    return [
        Compared("sidecars_compared", sidecars, None),
        Compared("shard_sets_compared_byte_by_byte", sets, None),
        Compared("shard_files_differing", differing, 0),
        Compared("ecsum_fields_differing", sidecar_faults, 0),
        Compared("sidecars_missing", obs.attempted - sidecars, 0),
        Compared("shard_sets_not_compared", whole - sets, 0),
        Compared("backend_not_on_device", not_device, 0),
        Compared("fallback_batches", fallen, 0),
    ]


def teardown(st: State) -> None:
    st.cluster.stop()
