"""Peer-fetch rebuild core (ec/peer_rebuild.py) under an injected byte
transport: verify-and-exclude across the wire, retry/exclusion/replan,
clean refusal with no partial publish, and idempotent re-runs across
crash windows. The server/gRPC layer on top is covered by
tests/test_ec_cluster_chaos.py.
"""

from __future__ import annotations

import os
import re

import numpy as np
import pytest

from seaweedfs_tpu import faults
from seaweedfs_tpu.ec import (
    CpuBackend,
    ECContext,
    ECError,
    PeerCorruptError,
    PeerFetchTransient,
    rebuild_from_peers,
)
from seaweedfs_tpu.ec.bitrot import BitrotProtection, ShardChecksumBuilder
from seaweedfs_tpu.ec.peer_rebuild import staging_dir
from seaweedfs_tpu.utils.retry import RetryPolicy

CTX = ECContext(4, 2)
BLOCK = 4096
SHARD_SIZE = 3 * BLOCK + 57  # partial final granule on purpose

# zero-sleep policy: retry schedules run in no wall time
FAST = RetryPolicy(max_attempts=3, base_delay=0.0, max_delay=0.0, jitter=0.0)


def synth(tmp_path, local=(0, 1), seed=0):
    """RS-consistent shard set + v1 sidecar; only `local` shard files
    exist on disk. Returns (base, shard_bytes: sid -> bytes)."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (CTX.data_shards, SHARD_SIZE), dtype=np.uint8)
    parity = CpuBackend(CTX).encode(data)
    shards = np.concatenate([data, parity], axis=0)
    blobs = {i: shards[i].tobytes() for i in range(CTX.total)}
    builders = [ShardChecksumBuilder(BLOCK) for _ in range(CTX.total)]
    for i in range(CTX.total):
        builders[i].write(blobs[i])
    base = str(tmp_path / "1")
    BitrotProtection.from_builders(CTX, builders, generation=3).save(
        base + ".ecsum"
    )
    for i in local:
        with open(base + CTX.to_ext(i), "wb") as f:
            f.write(blobs[i])
    return base, blobs


def serving_fetch(blobs, log=None):
    def fetch(peer, sid, off, size):
        if log is not None:
            log.append((peer, sid, off, size))
        return blobs[sid][off : off + size]

    return fetch


ALL_PEERS = {sid: ["peerB"] for sid in range(CTX.total)}


def test_peer_fetch_rebuild_bit_identical(tmp_path):
    base, blobs = synth(tmp_path, local=(0, 1))
    calls = []
    rep = rebuild_from_peers(
        base,
        {2: ["peerB"], 3: ["peerB"], 4: ["peerB"]},
        serving_fetch(blobs, calls),
        targets=[5],
        backend=CpuBackend(CTX),
        policy=FAST,
    )
    assert rep.rebuilt == [5]
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]
    # fetched exactly k - local = 2 shards, lowest candidate ids first
    assert sorted(rep.fetched) == [2, 3]
    assert rep.local_sources == [0, 1] and not rep.excluded_peers
    assert not os.path.exists(staging_dir(base)), "staging not cleaned"
    # sources were never published locally (no duplicate minting)
    for sid in (2, 3, 4):
        assert not os.path.exists(base + CTX.to_ext(sid))


def test_enough_local_sources_fetches_nothing(tmp_path):
    base, blobs = synth(tmp_path, local=(0, 1, 2, 3))
    calls = []
    rep = rebuild_from_peers(
        base, ALL_PEERS, serving_fetch(blobs, calls),
        targets=[4], backend=CpuBackend(CTX), policy=FAST,
    )
    assert rep.rebuilt == [4] and not rep.fetched and not calls
    assert open(base + CTX.to_ext(4), "rb").read() == blobs[4]


def test_transient_failure_retries_then_succeeds(tmp_path):
    base, blobs = synth(tmp_path, local=(0, 1))
    state = {"failed": 0}

    def flaky(peer, sid, off, size):
        # first attempt of every (sid, off) dies mid-stream
        if (sid, off) not in state:
            state[(sid, off)] = True
            state["failed"] += 1
            raise PeerFetchTransient("connection reset mid-stream")
        return blobs[sid][off : off + size]

    rep = rebuild_from_peers(
        base, {2: ["peerB"], 3: ["peerB"]}, flaky,
        targets=[5], backend=CpuBackend(CTX), policy=FAST,
    )
    assert rep.rebuilt == [5] and state["failed"] >= 2
    assert not rep.excluded_peers, "transient failures must not exclude"
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_retry_exhaustion_on_every_sibling_refuses_clean(tmp_path):
    base, blobs = synth(tmp_path, local=(0, 1))

    def dead(peer, sid, off, size):
        raise PeerFetchTransient("peer down")

    with pytest.raises(ECError, match="refusing"):
        rebuild_from_peers(
            base, ALL_PEERS, dead,
            targets=[5], backend=CpuBackend(CTX), policy=FAST,
        )
    # clean refusal: nothing published, staging wiped, locals untouched
    assert not os.path.exists(base + CTX.to_ext(5))
    assert not os.path.exists(staging_dir(base))
    for sid in (0, 1):
        assert open(base + CTX.to_ext(sid), "rb").read() == blobs[sid]


def test_corrupt_peer_excluded_and_replanned(tmp_path):
    """A holder serving rot for ONE shard is excluded wholesale; the
    plan re-routes that shard to another holder of the same sid."""
    base, blobs = synth(tmp_path, local=(0, 1))

    def fetch(peer, sid, off, size):
        chunk = blobs[sid][off : off + size]
        if peer == "rotten" and sid == 2:
            return bytes([chunk[0] ^ 0xFF]) + chunk[1:]  # persistent rot
        return chunk

    rep = rebuild_from_peers(
        base,
        {2: ["rotten", "clean"], 3: ["clean"]},
        fetch,
        targets=[5],
        backend=CpuBackend(CTX),
        policy=FAST,
    )
    assert rep.rebuilt == [5] and rep.excluded_peers == ["rotten"]
    assert rep.fetched == {2: "clean", 3: "clean"}
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_corrupt_exclusion_below_k_refuses_no_partial_publish(tmp_path):
    """Every reachable holder serves rot: exclusion leaves < k sources
    and the rebuild refuses cleanly instead of publishing anything."""
    base, blobs = synth(tmp_path, local=(0, 1))

    def rotten(peer, sid, off, size):
        chunk = blobs[sid][off : off + size]
        return bytes([chunk[0] ^ 0x01]) + chunk[1:]

    with pytest.raises(ECError, match="refusing"):
        rebuild_from_peers(
            base, ALL_PEERS, rotten,
            targets=[5], backend=CpuBackend(CTX), policy=FAST,
        )
    assert not os.path.exists(base + CTX.to_ext(5))
    assert not os.path.exists(staging_dir(base))


def test_transient_wire_corruption_rereads_without_exclusion(tmp_path):
    """One corrupt read that verifies clean on the immediate re-read is
    wire noise, not a rotten peer: the holder stays in the plan."""
    base, blobs = synth(tmp_path, local=(0, 1))
    state = {"flipped": False}

    def once_flipped(peer, sid, off, size):
        chunk = blobs[sid][off : off + size]
        if not state["flipped"]:
            state["flipped"] = True
            return bytes([chunk[0] ^ 0x80]) + chunk[1:]
        return chunk

    rep = rebuild_from_peers(
        base, {2: ["peerB"], 3: ["peerB"]}, once_flipped,
        targets=[5], backend=CpuBackend(CTX), policy=FAST,
    )
    assert state["flipped"] and rep.rebuilt == [5]
    assert not rep.excluded_peers
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_reread_fetches_only_the_bad_granule(tmp_path):
    """Wire corruption in one granule re-reads ONLY that granule's byte
    range — the already-verified rest of the chunk comes from the first
    buffer (a whole-chunk redo both wastes wire traffic and used to risk
    splicing the redo's own unchecked corruption into staging)."""
    base, blobs = synth(tmp_path, local=(0, 1))
    state = {"calls": []}

    def flip_at(chunk, pos):
        return chunk[:pos] + bytes([chunk[pos] ^ 0x80]) + chunk[pos + 1 :]

    def shifty(peer, sid, off, size):
        chunk = blobs[sid][off : off + size]
        if sid == 2:
            state["calls"].append((off, size))
            if len(state["calls"]) == 1:
                return flip_at(chunk, BLOCK + 7)  # granule 1 bad
        return chunk

    rep = rebuild_from_peers(
        base, {2: ["peerB"], 3: ["peerB"], 4: ["peerB"]}, shifty,
        targets=[5], backend=CpuBackend(CTX), policy=FAST,
    )
    assert len(state["calls"]) == 2, "granule mismatch should force a re-read"
    redo_off, redo_size = state["calls"][1]
    assert (redo_off, redo_size) == (BLOCK, BLOCK), (
        "re-read must cover exactly the failed granule, not the chunk"
    )
    assert rep.rebuilt == [5] and not rep.excluded_peers
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]


def test_corrupt_local_source_excluded_and_replaced(tmp_path):
    """A present-but-corrupt local shard is never fed to Reed-Solomon
    (another peer source covers it) AND is regenerated in place — the
    verify-and-exclude contract, peer edition."""
    base, blobs = synth(tmp_path, local=(0, 1, 2))
    with open(base + CTX.to_ext(2), "r+b") as f:
        f.seek(100)
        f.write(b"\xff\xff")
    rep = rebuild_from_peers(
        base, {3: ["peerB"], 4: ["peerB"], 5: ["peerB"]},
        serving_fetch(blobs),
        targets=[], backend=CpuBackend(CTX), policy=FAST,
    )
    assert rep.corrupt_local == [2]
    assert 2 in rep.rebuilt
    assert open(base + CTX.to_ext(2), "rb").read() == blobs[2]


def test_refuses_without_sidecar(tmp_path):
    base, blobs = synth(tmp_path, local=(0, 1))
    os.unlink(base + ".ecsum")
    with pytest.raises(ECError, match="ecsum"):
        rebuild_from_peers(
            base, ALL_PEERS, serving_fetch(blobs),
            targets=[5], backend=CpuBackend(CTX), policy=FAST,
        )


def test_crash_between_publishes_rerun_converges(tmp_path):
    """Crash after the first target publish: the re-run regenerates the
    remaining targets idempotently; already-published ones verify good
    and are untouched."""
    base, blobs = synth(tmp_path, local=(0, 1))
    with faults.injected(
        "ec.peer_rebuild.after_publish", faults.crash(), when=faults.nth_call(1)
    ):
        with pytest.raises(faults.InjectedCrash):
            rebuild_from_peers(
                base, ALL_PEERS, serving_fetch(blobs),
                targets=[4, 5], backend=CpuBackend(CTX), policy=FAST,
            )
    published = [
        sid for sid in (4, 5) if os.path.exists(base + CTX.to_ext(sid))
    ]
    assert len(published) == 1, "crash fired after exactly one publish"
    # stale staging from the crash is swept by the re-run
    rep = rebuild_from_peers(
        base, ALL_PEERS, serving_fetch(blobs),
        targets=[4, 5], backend=CpuBackend(CTX), policy=FAST,
    )
    assert rep.rebuilt == [sid for sid in (4, 5) if sid not in published]
    for sid in (4, 5):
        assert open(base + CTX.to_ext(sid), "rb").read() == blobs[sid]
    assert not os.path.exists(staging_dir(base))


def test_stale_staging_leftovers_are_swept(tmp_path):
    base, blobs = synth(tmp_path, local=(0, 1))
    sdir = staging_dir(base)
    os.makedirs(sdir)
    with open(os.path.join(sdir, "1.ec05.fetching"), "wb") as f:
        f.write(b"junk from a crashed run")
    rep = rebuild_from_peers(
        base, ALL_PEERS, serving_fetch(blobs),
        targets=[5], backend=CpuBackend(CTX), policy=FAST,
    )
    assert rep.rebuilt == [5]
    assert open(base + CTX.to_ext(5), "rb").read() == blobs[5]
    assert not os.path.exists(sdir)


def test_peer_corrupt_error_carries_context():
    e = PeerCorruptError("p1", 7, 3)
    assert e.peer == "p1" and e.shard == 7 and "granule 3" in str(e)


# ------------------------------------------ the sources stream to the chip


def reference_shards(blobs):
    """The plain encode of the data shards (ops/gf256.py, numpy): what
    every regenerated shard must equal byte for byte."""
    from seaweedfs_tpu.ops.gf256 import ReedSolomon

    data = np.stack([
        np.frombuffer(blobs[i], dtype=np.uint8) for i in range(CTX.data_shards)
    ])
    rs = ReedSolomon(CTX.data_shards, CTX.parity_shards)
    full = np.concatenate([data, rs.encode(data)], axis=0)
    return {i: full[i].tobytes() for i in range(CTX.total)}


def landing_fetch_into(blobs, rot=None, no_plane=(), log=None):
    """A shard plane in process: lands the range in `dst` and returns
    the granule CRCs of what it landed, as the native ingress does.
    `rot(peer, sid, off, chunk) -> chunk` bends what a holder sends;
    peers in `no_plane` have no plane."""
    from seaweedfs_tpu.ec.peer_rebuild import PeerPlaneUnavailable
    from seaweedfs_tpu.utils.crc import crc32c

    def fetch_into(peer, sid, off, size, dst, granule):
        if peer in no_plane:
            raise PeerPlaneUnavailable(f"{peer}: connection refused")
        chunk = blobs[sid][off : off + size]
        if rot is not None:
            chunk = rot(peer, sid, off, chunk)
        if log is not None:
            log.append((peer, sid, off, size))
        dst[:size] = np.frombuffer(chunk, dtype=np.uint8)
        return np.array(
            [crc32c(chunk[i : i + granule]) for i in range(0, size, granule)],
            dtype=np.uint32,
        )

    return fetch_into


@pytest.fixture
def batches_of_two_granules(monkeypatch):
    """A heal of two batches (two granules, then one and a ragged tail),
    so that a source streams batch by batch."""
    from seaweedfs_tpu.ec import peer_rebuild as pr

    monkeypatch.setattr(pr, "DEFAULT_BATCH", 2 * BLOCK)


@pytest.mark.parametrize("plane", ["native", "stream"])
def test_streamed_heal_is_the_reference_and_stages_no_source(
    tmp_path, batches_of_two_granules, plane
):
    """Both targets of a heal whose sources are two local shards and two
    peers' come out as the reference encode, byte for byte, and the
    staging directory never holds a source: at every request a peer
    answers, it holds at most the targets' temporaries."""
    base, blobs = synth(tmp_path, local=(0, 1), seed=5)
    sdir = staging_dir(base)
    seen: list[set] = []

    def look():
        seen.append(set(os.listdir(sdir)) if os.path.isdir(sdir) else set())

    def fetch(peer, sid, off, size):
        look()
        return blobs[sid][off : off + size]

    inner = landing_fetch_into(blobs)

    def fetch_into(*a):
        look()
        return inner(*a)

    rep = rebuild_from_peers(
        base, ALL_PEERS, fetch, targets=[4, 5], backend=CpuBackend(CTX),
        policy=FAST, fetch_into=fetch_into if plane == "native" else None,
    )
    want = reference_shards(blobs)
    assert rep.rebuilt == [4, 5] and sorted(rep.fetched) == [2, 3]
    for sid in (4, 5):
        assert open(base + CTX.to_ext(sid), "rb").read() == want[sid]
    assert set(rep.fetched_plane.values()) == {
        "native" if plane == "native" else "python"
    }
    assert len(seen) >= 4  # two sources, two batches each
    temps = {os.path.basename(base + CTX.to_ext(s)) + ".rebuilding" for s in (4, 5)}
    assert all(names <= temps for names in seen), seen
    for sid in (2, 3):  # nothing of a source was published either
        assert not os.path.exists(base + CTX.to_ext(sid))
    assert not os.path.exists(sdir)


@pytest.mark.parametrize("plane", ["native", "stream"])
def test_rot_mid_stream_excludes_the_holder_and_replans(
    tmp_path, batches_of_two_granules, plane
):
    """A holder that serves one rotten granule in the heal's SECOND
    batch, on either plane: the granule is re-read once, rot again, the
    holder is excluded after a batch of its rows had landed, and the heal
    starts over from another holder: still the reference, byte for
    byte, and nothing of the first try left behind."""
    base, blobs = synth(tmp_path, local=(0, 1), seed=6)
    log: list = []

    def rot(peer, sid, off, chunk):
        lo = 2 * BLOCK + 9 - off  # a byte of the second batch
        if peer == "rotten" and 0 <= lo < len(chunk):
            return chunk[:lo] + bytes([chunk[lo] ^ 0x40]) + chunk[lo + 1 :]
        return chunk

    def fetch(peer, sid, off, size):
        log.append((peer, sid, off, size))
        return rot(peer, sid, off, blobs[sid][off : off + size])

    rep = rebuild_from_peers(
        base, {2: ["rotten", "clean"], 3: ["clean"]}, fetch,
        targets=[5], backend=CpuBackend(CTX), policy=FAST,
        fetch_into=(
            landing_fetch_into(blobs, rot=rot, log=log)
            if plane == "native" else None
        ),
    )
    assert rep.rebuilt == [5] and rep.excluded_peers == ["rotten"]
    assert rep.fetched == {2: "clean", 3: "clean"}
    # the rotten holder answered the first batch before the second was rot
    assert ("rotten", 2, 0, 2 * BLOCK) in log
    assert ("rotten", 2, 2 * BLOCK, BLOCK) in log  # the one re-read
    assert open(base + CTX.to_ext(5), "rb").read() == reference_shards(blobs)[5]
    assert not os.path.exists(staging_dir(base))


def test_holders_dying_mid_stream_refuse_clean(tmp_path, batches_of_two_granules):
    """The holders of the sources a heal needs answer its first batch
    and then die: fewer than k sources are left, and the heal refuses
    with nothing published, nothing staged, the locals untouched."""
    base, blobs = synth(tmp_path, local=(0, 1), seed=7)

    def dying(peer, sid, off, size):
        if off >= 2 * BLOCK:
            raise PeerFetchTransient("connection reset by peer")
        return blobs[sid][off : off + size]

    with pytest.raises(ECError, match="refusing, nothing published"):
        rebuild_from_peers(
            base, {2: ["peerB"], 3: ["peerB"], 4: ["peerC"]}, dying,
            targets=[5], backend=CpuBackend(CTX), policy=FAST,
        )
    assert not os.path.exists(base + CTX.to_ext(5))
    assert not os.path.exists(staging_dir(base))
    assert sorted(os.listdir(tmp_path)) == ["1.ec00", "1.ec01", "1.ecsum"]


def test_a_holder_without_a_plane_is_read_over_the_stream(
    tmp_path, batches_of_two_granules
):
    """One holder has a shard plane, the other none: each source rides
    what its holder offers, both land verified, and the target is the
    reference."""
    base, blobs = synth(tmp_path, local=(0, 1), seed=8)
    streamed: list = []

    def fetch(peer, sid, off, size):
        streamed.append((peer, sid))
        return blobs[sid][off : off + size]

    rep = rebuild_from_peers(
        base, {2: ["planeless"], 3: ["withplane"]}, fetch,
        targets=[5], backend=CpuBackend(CTX), policy=FAST,
        fetch_into=landing_fetch_into(blobs, no_plane=("planeless",)),
    )
    assert rep.fetched_plane == {2: "python", 3: "native"}
    assert {p for p, _s in streamed} == {"planeless"}
    assert open(base + CTX.to_ext(5), "rb").read() == reference_shards(blobs)[5]


def test_an_armed_heal_books_where_its_sources_came_from(
    tmp_path, batches_of_two_granules
):
    """Armed, the heal's span holds the wait for the peers' rows
    (`source_land`) and how many source bytes came over each plane, from
    the local disk and to staging (none); the pipeline's stages hang off
    its `ec.rebuild` child and each source has an `ec.peer_fetch` span.
    Always on, `sw_ec_peer_rebuild_source_bytes_total{plane}` counts the
    same bytes."""
    from seaweedfs_tpu.utils import metrics as M
    from seaweedfs_tpu.utils import trace

    base, blobs = synth(tmp_path, local=(0,), seed=9)
    before = dict(M.ec_peer_rebuild_source_bytes_total.snapshot())
    trace.configure(enabled=True)
    try:
        trace.reset()
        rep = rebuild_from_peers(
            base, {1: ["pA"], 2: ["pA"], 3: ["pB"]},
            serving_fetch(blobs), targets=[5], backend=CpuBackend(CTX),
            policy=FAST,
            fetch_into=landing_fetch_into(blobs, no_plane=("pB",)),
        )
        doc = next(d for d in trace.traces() if d["op"] == "ec.peer_rebuild")
    finally:
        trace.configure(enabled=False)
        trace.reset()
    assert rep.rebuilt == [5]
    attrs = doc["attrs"]
    assert attrs["source_bytes_plane"] == 2 * SHARD_SIZE
    assert attrs["source_bytes_stream"] == SHARD_SIZE
    assert attrs["source_bytes_local"] == SHARD_SIZE
    assert attrs["source_bytes_staged"] == 0
    assert doc["stages"]["source_land"]["count"] == 2  # one a batch
    kids = {}
    for ch in doc["children"]:
        kids.setdefault(ch["op"], []).append(ch)
    assert len(kids["ec.peer_fetch"]) == 3
    assert all("peer_fetch" in c["stages"] for c in kids["ec.peer_fetch"])
    (rb,) = kids["ec.rebuild"]
    assert {"disk_read", "write_sink", "fsync_publish"} <= set(rb["stages"])
    after = M.ec_peer_rebuild_source_bytes_total.snapshot()
    grew = {k[0]: after[k] - before.get(k, 0) for k in after}
    assert grew == {
        "native": 2 * SHARD_SIZE, "stream": SHARD_SIZE, "local": SHARD_SIZE,
    }


@pytest.mark.parametrize("batch", [0, 1])
def test_a_transient_plane_failure_mid_stream_is_retried_on_the_plane(
    tmp_path, batches_of_two_granules, batch
):
    """A holder's plane drops the connection on the first request of one
    batch: the request is asked again on the plane, the holder stays in
    the plan, and the heal is the reference."""
    base, blobs = synth(tmp_path, local=(0, 1), seed=10 + batch)
    inner = landing_fetch_into(blobs)
    dropped: list = []

    def fetch_into(peer, sid, off, size, dst, granule):
        if off == 2 * BLOCK * batch and (sid, off) not in dropped:
            dropped.append((sid, off))
            raise PeerFetchTransient("connection reset by peer")
        return inner(peer, sid, off, size, dst, granule)

    rep = rebuild_from_peers(
        base, {2: ["pA"], 3: ["pB"]}, serving_fetch(blobs), targets=[4, 5],
        backend=CpuBackend(CTX), policy=FAST, fetch_into=fetch_into,
    )
    assert sorted(dropped) == [(2, 2 * BLOCK * batch), (3, 2 * BLOCK * batch)]
    assert rep.rebuilt == [4, 5] and not rep.excluded_peers
    assert rep.fetched_plane == {2: "native", 3: "native"}
    want = reference_shards(blobs)
    for sid in (4, 5):
        assert open(base + CTX.to_ext(sid), "rb").read() == want[sid]


def test_a_plane_that_goes_away_mid_stream_leaves_its_rows_to_the_stream(
    tmp_path, batches_of_two_granules
):
    """A holder answers the first batch on its plane, then the plane
    refuses: the rest of that source rides the `fetch` stream, the
    holder is not excluded, and the heal is the reference."""
    from seaweedfs_tpu.ec.peer_rebuild import PeerPlaneUnavailable

    base, blobs = synth(tmp_path, local=(0, 1), seed=12)
    inner = landing_fetch_into(blobs)
    streamed: list = []

    def fetch_into(peer, sid, off, size, dst, granule):
        if peer == "pA" and off > 0:
            raise PeerPlaneUnavailable("pA: connection refused")
        return inner(peer, sid, off, size, dst, granule)

    def fetch(peer, sid, off, size):
        streamed.append((peer, sid, off))
        return blobs[sid][off : off + size]

    rep = rebuild_from_peers(
        base, {2: ["pA"], 3: ["pB"]}, fetch, targets=[5],
        backend=CpuBackend(CTX), policy=FAST, fetch_into=fetch_into,
    )
    assert streamed == [("pA", 2, 2 * BLOCK)]
    assert rep.fetched_plane == {2: "python", 3: "native"} and not rep.excluded_peers
    assert open(base + CTX.to_ext(5), "rb").read() == reference_shards(blobs)[5]


def shard_files(d):
    return sorted(n for n in os.listdir(d) if re.fullmatch(r"1\.ec\d\d", n))


@pytest.mark.parametrize("point, nth", [
    ("ec.peer_rebuild.before_publish", 1),
    ("ec.peer_rebuild.after_publish", 1),
    ("ec.peer_rebuild.after_publish", 2),
])
def test_a_crash_around_publish_reruns_to_the_reference(
    tmp_path, batches_of_two_granules, point, nth
):
    """A heal of two targets whose sources stream from peers dies before
    its first publish, or after its first or its second: the canonical
    directory holds only whole targets, never a source, and a re-run
    converges to the reference with nothing left in staging."""
    base, blobs = synth(tmp_path, local=(0, 1), seed=13)
    want = reference_shards(blobs)
    with faults.injected(point, faults.crash(), when=faults.nth_call(nth)):
        with pytest.raises(faults.InjectedCrash):
            rebuild_from_peers(
                base, ALL_PEERS, serving_fetch(blobs), targets=[4, 5],
                backend=CpuBackend(CTX), policy=FAST,
            )
    on_disk = shard_files(tmp_path)
    published = {"ec.peer_rebuild.before_publish": [], "ec.peer_rebuild.after_publish": [4, 5][:nth]}[point]
    assert on_disk == ["1.ec00", "1.ec01"] + [f"1.ec{s:02d}" for s in published]
    for sid in published:
        assert open(base + CTX.to_ext(sid), "rb").read() == want[sid]
    rep = rebuild_from_peers(
        base, ALL_PEERS, serving_fetch(blobs), targets=[4, 5],
        backend=CpuBackend(CTX), policy=FAST,
        fetch_into=landing_fetch_into(blobs),
    )
    assert rep.rebuilt == sorted(set([4, 5]) - set(published))
    for sid in (4, 5):
        assert open(base + CTX.to_ext(sid), "rb").read() == want[sid]
    assert not os.path.exists(staging_dir(base))
    assert shard_files(tmp_path) == ["1.ec00", "1.ec01", "1.ec04", "1.ec05"]
