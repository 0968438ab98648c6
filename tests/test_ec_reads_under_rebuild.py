"""Reads of one EC volume while another is rebuilt on the same server
(ISSUE 33): a dead server took the same shards of both, `ec.rebuild`
has reached volume 2, volume 1 still serves every GET, the needles on
its lost shards by on-the-fly decode. One store, one `QueueScope`, one
`DeviceQueue`: foreground reconstructions and recovery batches meet
there, and what the queue says about that meeting is checked here too.

References, which import nothing of the program: the body written under
a file id (the seeded volume of `ecbench/data.py`); of a reconstructed
interval, `ecbench/reference_decode.py`'s decode of the shards left; of
a rebuilt shard and its `.ecsum`, `ecbench/reference.py`'s encode of the
volume's `.dat`. The traffic is the cell's own sampler and the rebuild
loop the cell's own (`ecbench/drivers/`). 8 MiB a volume, the CPU.
"""

from __future__ import annotations

import dataclasses
import functools
import http.client
import json
import os
import shutil
import threading
import urllib.request

import numpy as np
import pytest

from ecbench import cluster as C
from ecbench import data as D
from ecbench import harness, reference_decode
from ecbench import reference as R
from ecbench.layerlib import walk
from seaweedfs_tpu.ec.backend import get_backend
from seaweedfs_tpu.server import volume_server as VS
from seaweedfs_tpu.utils import trace

from test_device_queue import _until

CFG = harness.load_json(harness.HERE / "configs" / "vol1g-x2-10p4-recovering.json")
LAYOUT, PLAN = CFG["layout"], CFG["needles"]
K, M = int(LAYOUT["data_shards"]), int(LAYOUT["parity_shards"])
SEED = 2**31 + 33
GET_VID, OP_VID = 1, 2
SMALL_CACHE = 512 << 10  # under one lost shard's extents
REBUILDS = 5


@pytest.fixture(scope="module")
def drivers():
    mixed = harness.load_module("drivers", "gets_under_rebuild")
    return mixed, mixed.Y, mixed.V


@pytest.fixture(scope="module")
def sources(tmp_path_factory):
    """{vid: (the seeded volume, the reference encode of its .dat)}."""
    d = str(tmp_path_factory.mktemp("under_rebuild"))
    out = {}
    for vid in (GET_VID, OP_VID):
        vol = D.fabricate_volume(d, vid, SEED, 8 << 20, PLAN)
        dat = np.fromfile(vol.base + ".dat", dtype=np.uint8)
        out[vid] = (vol, R.encode(dat, LAYOUT))
    return out


class Mixed:
    """Both volumes encoded on one in-process master and volume server,
    `lost` gone from both: volume 1's unlinked and unmounted, volume 2's
    unmounted and deleted, as the cell's set-up leaves them."""

    def __init__(self, drivers, sources, tmp_path, monkeypatch, lost, cache_bytes, traced=False):
        _mixed, self.Y, self.V = drivers
        vol_dir, src_dir, keep_dir = (str(tmp_path / d) for d in ("vol", "src", "kept"))
        for d in (vol_dir, src_dir, keep_dir):
            os.makedirs(d)
        self.vols, self.want = {}, {}
        for vid, (vol, want) in sources.items():
            for ext in (".dat", ".idx"):
                shutil.copy(vol.base + ext, vol_dir)
            self.vols[vid] = dataclasses.replace(vol, base=os.path.join(vol_dir, str(vid)))
            self.want[vid] = want
        # a window of its own makes the store's scope its own: what the
        # queue counts below is this server's and nobody else's
        monkeypatch.setattr(
            VS, "VolumeServer", functools.partial(VS.VolumeServer, ec_queue_window=4)
        )
        self.lost = tuple(lost)
        self.cl = cl = C.Cluster(
            vol_dir, {"ec_backend": "cpu", "ec_interval_cache_mb": 1 if cache_bytes else 0}, traced
        )
        try:
            if cache_bytes:
                cl.vs.store.ec_interval_cache.capacity = cache_bytes
            for vid in self.vols:
                cl.wait_volume_listed(vid)
                cl.shell(f"ec.encode -volumeId {vid}")
            self.ev = cl.vs.store.find_ec_volume(GET_VID)
            for sid in self.lost:
                os.unlink(self.vols[GET_VID].base + R.shard_ext(sid))
            cl.unmount_shards(GET_VID, self.lost)
            self.ops = self.V.State(
                cluster=cl, volumes=[self.vols[OP_VID]], src_dir=src_dir, op="ec.rebuild",
                lost=self.lost, total_shards=K + M, op_bytes={}, keep_dir=keep_dir,
            )
            self.V._reset(self.ops, self.vols[OP_VID])
            for _vid, base in self.ops.kept:  # the encode's pair is not a rebuild's
                for sid in self.lost:
                    os.unlink(base + R.shard_ext(sid))
            self.ops.kept.clear()
            self.queue = cl.vs.store.ec_scheduler.for_backend(get_backend("cpu", K, M))
        except BaseException:
            self.stop()
            raise

    def rebuild(self) -> None:
        vol = self.vols[OP_VID]
        self.V._do(self.ops, OP_VID)
        self.V._note_sidecar(self.ops, vol)
        self.V._reset(self.ops, vol)

    def rebuilt_faults(self) -> tuple[int, int]:
        """(shard files differing from the reference encode over every
        kept pair, `.ecsum` fields differing over every sidecar)."""
        want = self.want[OP_VID]
        files = sum(R.compare_shards(base, want, self.lost) for _vid, base in self.ops.kept)
        fields = sum(R.compare_sidecar(raw, want) for _vid, raw in self.ops.sidecars)
        return files, fields

    def on_lost(self) -> set[int]:
        vol = self.vols[GET_VID]
        return {
            i for sid in self.lost if sid < K
            for i in self.Y.G.needles_on_shard(vol, sid, LAYOUT)
        }

    def get(self, conn, i: int) -> bytes:
        status, body = self.Y.G._get(conn, self.vols[GET_VID].fid(i))
        assert status == 200, (status, body[:200])
        return body

    def connect(self) -> http.client.HTTPConnection:
        return http.client.HTTPConnection(*self.cl.volume_host, timeout=60)

    def http(self, path: str) -> bytes:
        with urllib.request.urlopen(f"http://localhost:{self.cl.vs.port}{path}", timeout=30) as r:
            return r.read()

    def stop(self) -> None:
        self.cl.stop()
        trace.configure(enabled=False, slow_op_s=0.0)
        trace.reset()


@pytest.fixture
def mixed(drivers, sources, tmp_path, monkeypatch, request):
    lost, cache_bytes, traced = request.param
    m = Mixed(drivers, sources, tmp_path, monkeypatch, lost, cache_bytes, traced)
    yield m
    m.stop()


def case(lost, cache_bytes, traced=False):
    name = "lost_" + "_".join(map(str, lost)) + ("-small_cache" if cache_bytes else "-no_cache")
    return pytest.param((lost, cache_bytes, traced), id=name + ("-armed" if traced else ""))


# ------------------------------------- both classes, against the references


@pytest.mark.parametrize(
    "mixed", [case(lost, cache) for lost in ((1, 8), (3, 11), (1,)) for cache in (0, SMALL_CACHE)],
    indirect=True,
)
def test_zipf_reads_of_one_volume_while_the_other_is_rebuilt_five_times(mixed):
    vol, n = mixed.vols[GET_VID], len(mixed.vols[GET_VID].sizes)
    on_lost = mixed.on_lost()
    assert on_lost and len(on_lost) < n
    built: list[tuple[int, int, bytes]] = []
    real = mixed.ev._reconstruct_range

    def recording(shard_id, offset, size, prot=None):
        out = real(shard_id, offset, size, prot)
        built.append((shard_id, offset, out))
        return out

    mixed.ev._reconstruct_range = recording
    by_rank = mixed.Y.popularity(n, 24).tolist()
    done = threading.Event()
    wrong: list[int] = []
    asked: set[int] = set()
    errors: list[BaseException] = []

    def client(w: int) -> None:
        conn = mixed.connect()
        try:
            ranks = mixed.Y.zipf_ranks(SEED, w, n, 0.99)
            sent = 0
            while sent < 30 or (not done.is_set() and sent < 2000):
                i = by_rank[next(ranks)]
                asked.add(i)
                if mixed.get(conn, i) != vol.body(i):
                    wrong.append(i)
                sent += 1
        except BaseException as e:  # noqa: BLE001 - shown below
            errors.append(e)
        finally:
            conn.close()

    before = mixed.queue.stats()
    threads = [threading.Thread(target=client, args=(w,), daemon=True) for w in range(8)]
    for t in threads:
        t.start()
    try:
        for _ in range(REBUILDS):
            mixed.rebuild()
    finally:
        done.set()
        for t in threads:
            t.join(120)
    assert not any(t.is_alive() for t in threads) and not errors, errors
    # the reads: every body the seeded one, both kinds of needle drawn
    assert not wrong
    assert asked & on_lost and asked - on_lost
    # every reconstructed interval is the plain decode of the shards left
    pieces = mixed.want[GET_VID].pieces
    lost_data = [s for s in mixed.lost if s < K]
    want = reference_decode.decode(
        {s: np.concatenate(pieces[s]) for s in range(K + M) if s not in mixed.lost},
        lost_data, K, M,
    )
    assert built
    for shard_id, offset, out in built:
        assert out == want[shard_id][offset : offset + len(out)].tobytes()
    # the rebuilds: every pair and every sidecar the reference encode's
    assert len(mixed.ops.kept) == len(mixed.ops.sidecars) == REBUILDS
    assert mixed.rebuilt_faults() == (0, 0)
    assert mixed.cl.backend_faults(K, M)[1] == 0  # no fallback batch
    # one queue admitted both classes, and holds nothing now
    after = mixed.queue.stats()
    grew = {c: after[c]["admitted"] - before[c]["admitted"] for c in after}
    assert grew["foreground"] >= len(built) > 0 and grew["recovery"] >= REBUILDS
    used = [
        q for q in mixed.cl.vs.store.ec_scheduler.stats_snapshot()
        if any(c["admitted"] for c in q["classes"].values())
    ]
    assert len(used) == 1 and used[0]["classes"] == after
    assert all(c["inflight"] == 0 and c["depth"] == 0 for c in after.values())
    assert after["foreground"]["slot_s"] > 0 and after["recovery"]["slot_s"] > 0
    for c in after.values():  # a blocked wait is a wait
        assert sum(c["blocked_s"].values()) <= c["wait_s_total"] + 1e-6


# ---------------------------------- what the queue says when the two meet


@pytest.mark.parametrize(
    "mixed", [case((1, 8), 0), case((1, 8), 0, traced=True)], indirect=True
)
def test_a_reconstruction_that_finds_the_window_full_of_recovery_is_counted_and_shown(mixed):
    armed = trace.armed
    vol = mixed.vols[GET_VID]
    i = min(mixed.on_lost())
    hog = mixed.queue.stream("recovery")
    tickets = [hog.dispatch(lambda: None, 1 << 20)[0] for _ in range(mixed.queue.window)]
    got: list[bytes] = []

    def one_get() -> None:
        conn = mixed.connect()
        try:
            got.append(mixed.get(conn, i))
        finally:
            conn.close()

    t = threading.Thread(target=one_get, daemon=True)
    t.start()
    try:
        _until(lambda: mixed.queue.stats()["foreground"]["depth"] == 1, "the GET at the queue")
    finally:
        hog.close()  # every slot back
    t.join(60)
    assert got == [bytes(vol.body(i))]
    (snap,) = mixed.cl.vs.store.ec_scheduler.stats_snapshot()
    fg = snap["classes"]["foreground"]
    assert fg["blocked"] == {"recovery": 1} and fg["blocked_s"]["recovery"] > 0
    assert fg["blocked_s"]["recovery"] <= fg["wait_s_total"]  # the set-up's encodes waited too
    assert snap["classes"]["recovery"]["slot_s"] > 0 and len(tickets) == 4
    text = mixed.http("/metrics").decode()
    label = f'chip="{mixed.queue.label}"'
    for series in (  # process-wide counters: other tests' servers had this label too
        f'sw_ec_queue_blocked_total{{cls="foreground",by="recovery",{label}}}',
        f'sw_ec_queue_blocked_seconds_total{{cls="foreground",by="recovery",{label}}}',
        f'sw_ec_queue_slot_seconds_total{{cls="recovery",{label}}}',
    ):
        (line,) = [ln for ln in text.splitlines() if ln.startswith(series + " ")]
        assert float(line.rsplit(" ", 1)[1]) > 0, line
    if not armed:
        assert trace.traces() == []
        return

    def reads() -> list[dict]:
        docs = json.loads(mixed.http("/debug/traces?format=spans"))
        docs = docs["traces"] if isinstance(docs, dict) else docs
        return [
            d for root in docs for d in walk(root)
            if d["op"] == "ec.degraded_read"
        ]

    _until(lambda: reads(), "the GET's root in the ring")  # it lands after the response
    (read,) = reads()
    (ev,) = [e for e in read["events"] if e["name"] == "window_full"]
    assert ev["attrs"] == {"by": "recovery", "held": {"recovery": 4}}
    assert read["stages"]["admission_wait"]["seconds"] == pytest.approx(
        fg["blocked_s"]["recovery"], abs=1e-6
    )


@pytest.mark.parametrize("mixed", [case((1, 8), 0)], indirect=True)
def test_a_rebuild_that_finds_the_window_full_of_reconstructions_waits_and_is_exact(mixed):
    fg = mixed.queue.stream("foreground")
    tickets = [fg.dispatch(lambda: None, 1 << 16)[0] for _ in range(mixed.queue.window)]
    errors: list[BaseException] = []

    def rebuild() -> None:
        try:
            mixed.rebuild()
        except BaseException as e:  # noqa: BLE001 - shown below
            errors.append(e)

    t = threading.Thread(target=rebuild, daemon=True)
    t.start()
    try:
        _until(lambda: mixed.queue.stats()["recovery"]["depth"] == 1, "the rebuild at the queue")
        assert not mixed.ops.kept  # nothing rebuilt while it waits
    finally:
        for ticket in tickets:
            fg.release(ticket)
    t.join(120)
    assert not t.is_alive() and not errors, errors
    st = mixed.queue.stats()
    assert st["recovery"]["blocked"] == {"foreground": 1}
    assert st["recovery"]["blocked_s"]["foreground"] > 0
    assert st["foreground"]["blocked"] == {}
    assert mixed.rebuilt_faults() == (0, 0) and len(mixed.ops.kept) == 1


# ------------------------------------------------- the cell's own pieces


@pytest.mark.parametrize("now, done, over", [
    (19.9, True, False),  # the window is not over
    (20.5, False, False),  # over, but a rebuild still runs
    (20.5, True, True),
])
def test_the_clients_go_on_until_the_window_is_over_and_the_last_rebuild_done(
    drivers, now, done, over
):
    mixed_driver = drivers[0]
    event = threading.Event()
    if done:
        event.set()
    deadline = 100.0 + mixed_driver.Until(20.0, event)  # as `t_begin + cell.seconds`
    assert (100.0 + now >= deadline) is over  # as `t0 >= deadline`


def test_overlapped_pairs_are_foreground_batches_admitted_while_a_rebuild_ran(drivers):
    mixed_driver = drivers[0]
    q = ("CpuBackend", "chip0")

    def counts(fg, rec, other=0):
        return {(q, "foreground"): fg, (q, "recovery"): rec, (("X", "y"), "foreground"): other}

    class Ops:
        pass

    st = Ops()
    st.ops = Ops()
    st.ops.cluster = Ops()
    st.ops.cluster.commands = [
        ("ec.rebuild -volumeId 2", counts(10, 0), counts(13, 7)),
        ("ec.encode -volumeId 2", counts(13, 7), counts(20, 7)),  # not a rebuild
        ("ec.rebuild -volumeId 2", counts(20, 7), counts(20, 14)),  # nothing met it
    ]
    assert mixed_driver.overlap(st) == (3, 1)
    st.ops.cluster.commands = st.ops.cluster.commands[2:]
    assert mixed_driver.overlap(st) == (0, 0)
    # a second queue that took the reads: two classes, two queues
    st.ops.cluster.commands = [
        ("ec.rebuild -volumeId 2", counts(0, 0), counts(0, 7, other=5)),
    ]
    assert mixed_driver.overlap(st) == (5, 0)
