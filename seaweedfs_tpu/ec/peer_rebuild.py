"""Peer-fetch EC rebuild: recover when no single server holds k shards.

Per-server rebuild (ec/rebuild.py) refuses when fewer than k source
shards are on local disk — correct, but on a balanced cluster EVERY
holder is a subset holder, so a quarantined shard could never be
regenerated anywhere. The reference solves this at the maintenance
layer (ec.rebuild collects shards onto one node first); this module is
the streaming equivalent: stream just enough sibling shards from peer
holders, batch by batch, straight into the rows of the staged/scheduled
rebuild pipeline's batch matrices (the holder's native shard plane
where it has one, the shard-read RPC where not), rebuild on the TPU,
and publish only the regenerated targets.

Present-but-corrupt local shards whose rot is pinned to specific 64 KiB
leaves (v2 sidecar) are repaired at LEAF granularity first: only the
rotten leaves' byte ranges are fetched from k range-verified sources
(local good shards from disk, the rest over the ranged shard-read RPC)
and patched in place under the crash-consistent repair journal
(ec/repair_journal.py) — ~k·64 KiB of wire per rotten leaf instead of
~k·shard. Only what leaf repair cannot fix takes the whole-shard
fetch/rebuild/publish path below.

Correctness envelope (the same verify-and-exclude rules as the local
rebuild, extended across the wire):

- every granule from a peer is verified against the .ecsum sidecar at
  the sidecar's own granularity as it lands, before it reaches
  Reed-Solomon — a peer serving corrupt bytes is excluded (after one
  immediate re-read to rule out transient wire corruption) and the plan
  re-routes to another holder or another shard, the heal's batches
  starting over (nothing is published before the last one); transient
  failures (RPC errors, torn/short streams) retry under utils/retry.py
  before the holder is abandoned;
- fewer than k verified sources reachable = clean refusal: staging is
  wiped, nothing is published, the canonical files are untouched;
- no source byte is written to disk: local sources are read where they
  lie, the peers' rows land in the batch matrix (`PeerSources`), so the
  local server never holds copies of shards the master placed on peers
  — no duplicate minting, even across crashes;
- regenerated targets publish with the local rebuild's own machinery
  (temp + fsync + sidecar re-verify + atomic rename inside a staging
  directory next to the volume, then one rename per target into the
  canonical directory), so a re-run after any crash window converges
  idempotently.

The actual byte transport is injected (`fetch`), so the core is
testable without servers; server/volume_server.py wires it to the
VolumeEcShardRead RPC with the generation fence, and distributes
regenerated shards the local server does not own to planned holders
(ec/placement.py).
"""

from __future__ import annotations

import contextvars
import os
import shutil
from concurrent.futures import ThreadPoolExecutor, wait
from dataclasses import dataclass, field

import numpy as np

from .. import faults
from ..utils import metrics as M
from ..utils import trace
from ..utils.crc import crc32c
from ..utils.fs import fsync_dir
from ..utils.glog import logger
from ..utils.retry import RetryError, RetryPolicy, retry_call
from .bitrot import BitrotError, BitrotProtection
from .context import ECContext, ECError
from .encoder import DEFAULT_BATCH
from .rebuild import _attempt_rebuild
from .repair_journal import (
    apply_leaf_repair,
    leaf_verdict,
    patched_byte_ranges,
    reconstruct_leaves,
)
from .volume_info import VolumeInfo

log = logger("ec.peer")

# Transient fetch failures (RPC errors, torn/short streams) retry
# quickly and give up fast: with several candidate holders per shard, a
# dead peer should cost milliseconds, not a backoff tail. ECError is
# never retried — refusals are deterministic.
DEFAULT_FETCH_POLICY = RetryPolicy(
    max_attempts=3, base_delay=0.05, max_delay=0.5,
)

# Fetch request size: granule-aligned so the sidecar CRC verdict lands
# per chunk (bounded memory, early corrupt-peer detection).
FETCH_CHUNK = 1 << 20

# The native ingress lands straight in the batch matrix's row, so it
# can afford a wider window per request: fewer header round trips and
# Python-level chunk turnarounds on the zero-copy path.
NATIVE_FETCH_CHUNK = 4 << 20

STAGING_PREFIX = ".peerfetch-"


class PeerFetchTransient(Exception):
    """One fetch attempt failed in a retryable way (RPC error, short or
    torn stream). `fetch` implementations raise this for transport
    errors; persistent transients abandon the holder, not the plan."""


class PeerPlaneUnavailable(Exception):
    """The peer serves no native shard byte plane (ec/net_plane.py):
    `fetch_into` implementations raise this so the stream falls back to
    the Python `fetch` transport — a capability miss, not a failure,
    so it is never retried and never excludes the holder."""


class PeerCorruptError(Exception):
    """A peer served bytes that fail sidecar verification even on a
    re-read: the holder is serving rot and is excluded from the plan."""

    def __init__(self, peer: str, shard: int, granule: int):
        super().__init__(
            f"peer {peer} serves corrupt bytes for shard {shard} "
            f"(granule {granule})"
        )
        self.peer = peer
        self.shard = shard


@dataclass
class PeerRebuildReport:
    """What one peer-fetch rebuild attempt did."""

    rebuilt: list[int] = field(default_factory=list)
    fetched: dict[int, str] = field(default_factory=dict)  # sid -> peer
    # Which byte plane carried each fetched source ("native" = zero-copy
    # net-plane ingress straight into the batch matrix, "python" = the
    # bit-identical gRPC/bytes fallback).
    fetched_plane: dict[int, str] = field(default_factory=dict)
    local_sources: list[int] = field(default_factory=list)
    corrupt_local: list[int] = field(default_factory=list)
    excluded_peers: list[str] = field(default_factory=list)
    # Present-but-corrupt local shards whose rot was leaf-localized and
    # repaired IN PLACE under the repair journal, fetching only the
    # rotten leaves' byte ranges from peers (shard -> patched leaves).
    # These never enter the whole-shard rebuild.
    leaf_repaired: dict[int, list[int]] = field(default_factory=dict)
    # Bytes actually pulled over the wire for those ranged repairs
    # (including granule re-reads) — the ~k·64 KiB-per-leaf acceptance
    # number, vs ~k·shard for a full peer-fetch rebuild.
    repair_wire_bytes: int = 0
    # In-place patches applied this run (shard -> [(lo, hi), ...]): the
    # serving layer drops cached reconstructions over exactly these.
    patched_ranges: dict[int, list[tuple[int, int]]] = field(
        default_factory=dict
    )


def staging_dir(base: str) -> str:
    """Staging directory for one volume's peer-fetch rebuild (same
    filesystem as the volume, so hard links and renames work)."""
    d, name = os.path.split(base)
    return os.path.join(d, STAGING_PREFIX + name)


def _clear_staging(sdir: str) -> None:
    shutil.rmtree(sdir, ignore_errors=True)


def _verify_local(
    base: str, ctx: ECContext, prot: BitrotProtection, present: list[int]
) -> tuple[list[int], list[int]]:
    """(verified-good, corrupt) split of the local present shards. An
    unreadable or size-mismatched shard counts corrupt — it must never
    be fed to Reed-Solomon."""

    def check(i: int) -> bool:
        p = base + ctx.to_ext(i)
        try:
            if os.path.getsize(p) != prot.shard_sizes[i]:
                return True
            return bool(prot.verify_shard_file(p, i, stop_early=True))
        except OSError:
            return True

    if len(present) <= 1:
        flags = [check(i) for i in present]
    else:
        with ThreadPoolExecutor(max_workers=min(len(present), 8)) as ex:
            flags = list(ex.map(check, present))
    corrupt = [i for i, bad in zip(present, flags) if bad]
    return [i for i in present if i not in corrupt], corrupt


class PeerSourceError(Exception):
    """Rows of a batch did not land verified: `failures` lists
    (shard, peer, cause) for each, where cause is PeerCorruptError (the
    holder serves rot) or a transport failure that outlived its retries
    (PeerFetchTransient / RetryError)."""

    def __init__(self, failures: list[tuple[int, str, BaseException]]):
        super().__init__(
            "; ".join(f"shard {s} <- {p}: {e}" for s, p, e in failures)
        )
        self.failures = failures


class _Landing:
    """One batch's remote rows on their way (PeerSources.start)."""

    def __init__(self, sources: "PeerSources", futures: dict, local_bytes: int):
        self._sources = sources
        self._futures = futures
        self._local_bytes = local_bytes

    def join(self) -> None:
        """Wait for every row, landed or failed: the wait for the peers
        beyond the local read is the heal's `source_land`."""
        with trace.stage(self._sources.span, "source_land"):
            wait(self._futures.values())

    def check(self) -> None:
        failures = [
            (sid, self._sources.plan[sid], f.exception())
            for sid, f in self._futures.items()
            if f.exception() is not None
        ]
        if failures:
            raise PeerSourceError(failures)
        self._sources.booked("local", self._local_bytes)


class PeerSources:
    """The sources of one heal that are read from peers, streamed batch
    by batch into the rows of the pipeline's batch matrix (the `remote`
    of rebuild._attempt_rebuild): `plan` maps each source shard to the
    holder it is read from. Every granule is verified against the
    `.ecsum` sidecar as it lands; a mismatch is re-read once at granule
    width (wire noise) and a repeat is the holder serving rot.

    A row rides the holder's native shard plane (`fetch_into`: straight
    into the matrix row, the granule CRCs rolled during the copy-in)
    where the native plane is enabled, the fault registry is disarmed
    and the holder has a plane; the `fetch` stream (bytes, verified,
    copied into the row) where not. Nothing is written to disk.

    Armed, each source has an `ec.peer_fetch` span (stages `peer_fetch`,
    `crc_verify`) that its landing threads hold current, so the holders'
    `rpc.ec_shard_read` roots join the heal's trace; the heal's span gets
    `source_land` and the `source_bytes_*` attributes, and
    `sw_ec_peer_rebuild_source_bytes_total{plane}` counts always."""

    def __init__(
        self, plan: dict[int, str], n_local: int, prot: BitrotProtection,
        fetch, fetch_into, policy: RetryPolicy, span,
    ):
        from . import native_io

        self.plan = dict(plan)
        self.sids = sorted(plan)
        self.n_local = n_local
        self.prot = prot
        self.fetch = fetch
        self.fetch_into = fetch_into
        self.policy = policy
        self.span = span
        self.native = (
            fetch_into is not None and native_io.enabled()
            and not faults.active()
        )
        self.planes = {
            sid: ("native" if self.native else "python") for sid in self.sids
        }
        self._no_plane: set[str] = set()
        self._context = contextvars.copy_context()
        self._spans = {
            sid: trace.start(
                "ec.peer_fetch", name=f"shard {sid} <- {peer}", parent=span,
                peer=peer, shard=sid, bytes=prot.shard_sizes[sid],
            )
            for sid, peer in self.plan.items()
        }
        self._pool = ThreadPoolExecutor(
            max_workers=max(len(plan), 1), thread_name_prefix="ec-peer-source"
        )

    def close(self) -> None:
        self._pool.shutdown(wait=True)
        for sp in self._spans.values():
            trace.finish(sp)

    def booked(self, plane: str, n: int) -> None:
        if n <= 0:
            return
        M.ec_peer_rebuild_source_bytes_total.inc(n, plane=plane)
        if self.span is not None:
            self.span.count(_SOURCE_ATTR[plane], n)

    def start(self, off: int, width: int, rows: dict) -> _Landing:
        futures = {
            sid: self._pool.submit(
                self._context.copy().run, self._land, sid, off, rows[sid]
            )
            for sid in self.sids
        }
        return _Landing(self, futures, self.n_local * width)

    def _land(self, sid: int, off: int, row: np.ndarray) -> None:
        peer = self.plan[sid]
        sp = self._spans.get(sid)
        with trace.activate(sp):
            if self.native and peer not in self._no_plane:
                try:
                    self._land_native(sid, peer, off, row, sp)
                    return
                except PeerPlaneUnavailable as e:
                    log.info(
                        "peer %s has no native shard plane (%s); falling "
                        "back to the python fetch", peer, e,
                    )
                    self._no_plane.add(peer)
            self.planes[sid] = "python"
            self._land_python(sid, peer, off, row, sp)

    def _reread(self, peer: str, sid: int, at: int, n: int, gi: int, sp) -> bytes:
        """One immediate byte-level re-read of ONE granule (the
        transient-wire-corruption escape); a repeat mismatch is the peer
        serving rot."""
        _gsize, gcrcs = self.prot.verify_granularity(sid)
        g2 = self._get(peer, sid, at, n, sp)
        if gi >= len(gcrcs) or crc32c(g2) != gcrcs[gi]:
            raise PeerCorruptError(peer, sid, gi)
        return g2

    def _land_native(self, sid, peer, off, row, sp) -> None:
        gsize, gcrcs = self.prot.verify_granularity(sid)
        chunk = max(NATIVE_FETCH_CHUNK - NATIVE_FETCH_CHUNK % gsize, gsize)
        pos = 0
        while pos < len(row):
            n = min(chunk, len(row) - pos)
            at = off + pos
            dst = row[pos : pos + n]

            def attempt(at=at, n=n, dst=dst):
                return self.fetch_into(peer, sid, at, n, dst, gsize)

            with trace.stage(sp, "peer_fetch"):
                crcs = retry_call(
                    attempt, self.policy, retry_on=(PeerFetchTransient,),
                    describe=f"peer fetch {peer} shard {sid}",
                )
            self.booked("native", n)
            with trace.stage(sp, "crc_verify"):
                ngr = (n + gsize - 1) // gsize
                if crcs is None or len(crcs) != ngr:
                    raise PeerFetchTransient(
                        f"native ingress returned "
                        f"{0 if crcs is None else len(crcs)} granule CRCs "
                        f"for {ngr} granules"
                    )
                g0 = at // gsize
                for j in range(ngr):
                    if g0 + j < len(gcrcs) and int(crcs[j]) == gcrcs[g0 + j]:
                        continue
                    lo = j * gsize
                    glen = min(gsize, n - lo)
                    g2 = self._reread(peer, sid, at + lo, glen, g0 + j, sp)
                    dst[lo : lo + glen] = np.frombuffer(g2, dtype=np.uint8)
            pos += n

    def _get(self, peer: str, sid: int, at: int, n: int, sp) -> bytes:
        """`n` bytes of shard `sid` at `at` over the `fetch` stream."""
        with trace.stage(sp, "peer_fetch"):
            data = _fetch_exact(self.fetch, self.policy, peer, sid, at, n)
        self.booked("stream", n)
        return data

    def _land_python(self, sid, peer, off, row, sp) -> None:
        gsize, gcrcs = self.prot.verify_granularity(sid)
        chunk = max(FETCH_CHUNK - FETCH_CHUNK % gsize, gsize)
        pos = 0
        while pos < len(row):
            n = min(chunk, len(row) - pos)
            at = off + pos
            data = self._get(peer, sid, at, n, sp)
            with trace.stage(sp, "crc_verify"):
                for j in range(0, n, gsize):
                    gi = (at + j) // gsize
                    g = data[j : j + gsize]
                    if gi >= len(gcrcs) or crc32c(g) != gcrcs[gi]:
                        # re-read ONLY this granule's byte range: the rest
                        # of the chunk already passed its CRCs
                        g2 = self._reread(peer, sid, at + j, len(g), gi, sp)
                        data = data[:j] + g2 + data[j + gsize :]
            row[pos : pos + n] = np.frombuffer(data, dtype=np.uint8)
            pos += n


# span attribute of the heal (`ec.peer_rebuild`) for each source plane
_SOURCE_ATTR = {
    "native": "source_bytes_plane",
    "stream": "source_bytes_stream",
    "local": "source_bytes_local",
}


def _plan_sources(
    want_more: int, candidates: list[int], holders: dict[int, list[str]],
    excluded: set[str], unreachable: set[tuple[str, int]],
) -> dict[int, str] | None:
    """The first `want_more` candidate shards (lowest ids first) that
    some holder not excluded and not found unreachable for it serves,
    each with that holder; None where fewer are left."""
    plan: dict[int, str] = {}
    for sid in candidates:
        if len(plan) >= want_more:
            break
        peer = next(
            (p for p in holders[sid]
             if p not in excluded and (p, sid) not in unreachable),
            None,
        )
        if peer is not None:
            plan[sid] = peer
    return plan if len(plan) >= want_more else None


def _staged_source_bytes(sdir: str, sbase: str, ctx: ECContext,
                         targets: list[int]) -> int:
    """Bytes under the staging directory that are not a target's: what
    a heal wrote of its sources to disk (0: they went to the chip)."""
    outs = {
        os.path.basename(sbase + ctx.to_ext(i)) + suffix
        for i in targets for suffix in ("", ".rebuilding")
    }
    total = 0
    with os.scandir(sdir) as it:
        for e in it:
            if e.name not in outs and e.is_file(follow_symlinks=False):
                total += e.stat(follow_symlinks=False).st_size
    return total


def _fetch_exact(fetch, policy: RetryPolicy, peer: str, sid: int, at: int,
                 n: int) -> bytes:
    """Exactly `n` bytes of shard `sid` at `at` from `peer` over the
    `fetch` stream, transient failures retried under `policy`, with the
    client-side chaos point: a raised IOError is a transient fetch
    failure; a mutate corrupts the stream the way a rotten peer (or a
    bad NIC) would, which the caller's granule CRC must catch."""

    def attempt() -> bytes:
        try:
            faults.fire("ec.peer_fetch.read", peer=peer, shard=sid, offset=at)
            data = fetch(peer, sid, at, n)
        except (PeerFetchTransient, PeerCorruptError):
            raise
        except (IOError, OSError) as e:
            raise PeerFetchTransient(str(e)) from e
        data = faults.mutate(
            "ec.peer_fetch.read", data, peer=peer, shard=sid, offset=at
        )
        if len(data) != n:
            raise PeerFetchTransient(
                f"short read from {peer} for shard {sid}: "
                f"{len(data)}/{n} bytes at {at}"
            )
        return data

    return retry_call(
        attempt, policy, retry_on=(PeerFetchTransient,),
        describe=f"peer fetch {peer} shard {sid}",
    )


def _fetch_range_verified(
    peer: str,
    sid: int,
    lo: int,
    size: int,
    prot: BitrotProtection,
    fetch,
    policy: RetryPolicy,
    counter: list,
    sp=None,
) -> bytes:
    """Fetch ONE leaf-aligned byte range [lo, lo+size) of a sibling
    shard from `peer`, verifying every granule against the sidecar as
    it lands, as `PeerSources` verifies a source's rows. A granule
    that mismatches gets one immediate re-read (transient wire
    corruption); a repeat mismatch raises PeerCorruptError so the
    caller excludes the holder. `counter[0]` accumulates the bytes
    actually pulled over the wire (re-reads included)."""
    gsize, _ = prot.verify_granularity(sid)

    def get(off: int, n: int) -> bytes:
        with trace.stage(sp, "repair_fetch"):
            got = _fetch_exact(fetch, policy, peer, sid, off, n)
        counter[0] += len(got)
        return got

    data = get(lo, size)
    with trace.stage(sp, "crc_verify"):
        if not prot.verify_range(sid, lo, data):
            # pin the mismatch to its granule(s): one immediate re-read
            # each (transient wire corruption); a repeat mismatch is
            # the peer serving rot
            for j in range(0, size, gsize):
                g = data[j : j + gsize]
                if prot.verify_range(sid, lo + j, g):
                    continue
                g2 = get(lo + j, len(g))
                if not prot.verify_range(sid, lo + j, g2):
                    raise PeerCorruptError(peer, sid, (lo + j) // gsize)
                data = data[:j] + g2 + data[j + len(g) :]
    return data


def rebuild_from_peers(
    base: str,
    holders: dict[int, list[str]],
    fetch,
    *,
    ctx: ECContext | None = None,
    targets: list[int] | None = None,
    backend=None,
    scheduler=None,
    priority: str = "recovery",
    policy: RetryPolicy = DEFAULT_FETCH_POLICY,
    fetch_into=None,
    span=None,
) -> PeerRebuildReport:
    """Regenerate `targets` for the volume at `base`, fetching sibling
    source shards from peer holders when fewer than k verified-good
    shards are on local disk.

    `holders` maps shard id -> peer ids that serve it (the LOCAL server
    must already be excluded); `fetch(peer, shard_id, offset, size)`
    returns exactly `size` bytes or raises PeerFetchTransient.
    `fetch_into(peer, shard_id, offset, size, dst, granule)` is the
    OPTIONAL native-plane transport (ec/net_plane.make_fetch_into):
    lands the range directly in `dst` and returns the granule CRCs
    rolled during the copy-in, raises PeerPlaneUnavailable for peers
    without the plane — the sources' rows then ride it whenever the
    native plane is enabled and the fault registry is disarmed, with
    the `fetch` path as the bit-identical fallback.
    `targets=None` regenerates every shard that is not locally
    verified-good; an explicit list restricts regeneration to those ids
    (the server passes its legitimate-set union cluster-lost, the same
    no-duplicate-minting contract as the local rebuild RPC) —
    present-but-corrupt local shards are always replaced regardless.

    Fail-closed: no (or malformed) .ecsum refuses — peer bytes cannot
    be trusted unverified; fewer than k reachable verified sources
    refuses with nothing published and staging wiped.

    `span`: the heal's `ec.peer_rebuild` span where the caller opened
    it (and finishes it: the volume server times its distribution on
    it); None opens one here.
    """
    ecsum = base + ".ecsum"
    if not os.path.exists(ecsum):
        raise ECError(
            f"peer-fetch rebuild for {base} needs the .ecsum sidecar to "
            f"verify fetched streams; refusing"
        )
    try:
        prot = BitrotProtection.load(ecsum)
    except BitrotError as e:
        raise ECError(
            f"bitrot sidecar for {base} is malformed ({e}); refusing "
            f"peer-fetch rebuild"
        ) from e
    if ctx is None:
        vif = base + ".vif"
        if os.path.exists(vif):
            vi = VolumeInfo.load(vif)
            ctx = vi.ec_ctx
        if ctx is None:
            ctx = prot.ctx
    if prot.ctx != ctx:
        raise ECError(
            f"bitrot sidecar for {base} records ratio {prot.ctx} but the "
            f"volume config says {ctx}; refusing peer-fetch rebuild"
        )
    k = ctx.data_shards

    # Flight-recorder root for the whole peer-fetch rebuild (a child
    # when the holder's RPC span is active in this thread): per-peer
    # fetch child spans, the nested local rebuild, and the publish
    # renames all hang off it, so one cluster heal reads as one tree.
    own = span is None
    sp = span if not own else trace.start(
        "ec.peer_rebuild", name=os.path.basename(base), base=base,
        targets=("auto" if targets is None else sorted(targets)),
    )
    if sp is not None:
        for attr in _SOURCE_ATTR.values():
            sp.count(attr, 0)
        sp.count("source_bytes_staged", 0)
    try:
        with trace.activate(sp):
            return _rebuild_from_peers_span(
                base, holders, fetch, ctx, targets, backend, scheduler,
                priority, policy, prot, ecsum, k, sp, fetch_into,
            )
    finally:
        if own:
            trace.finish(sp)


def _rebuild_from_peers_span(
    base, holders, fetch, ctx, targets, backend, scheduler, priority,
    policy, prot, ecsum, k, sp, fetch_into=None,
) -> PeerRebuildReport:
    report = PeerRebuildReport()
    present = [
        i for i in range(ctx.total) if os.path.exists(base + ctx.to_ext(i))
    ]
    with trace.stage(sp, "verify"):
        good_local, corrupt_local = _verify_local(base, ctx, prot, present)
    report.local_sources = list(good_local)
    report.corrupt_local = list(corrupt_local)
    excluded: set[str] = set()

    # ---- leaf-granular ranged repair of present-but-corrupt locals ----
    # When the rot is pinned to specific leaves (v2 sidecar, full-length
    # file), fetch ONLY those leaves' byte ranges from k verified
    # sources — local good shards read from disk, the remainder pulled
    # from peers through the ranged shard-read RPC — and patch the
    # canonical file in place under the repair journal. Wire cost:
    # ~k·64 KiB per rotten leaf instead of ~k·shard. Anything this
    # cannot fix stays in corrupt_local and takes the whole-shard path.
    if prot.has_leaves and corrupt_local:
        for sid in list(corrupt_local):
            path = base + ctx.to_ext(sid)
            bad = leaf_verdict(path, sid, prot)
            if bad is None:
                continue  # size rot / unreadable: whole-shard replacement
            if not bad:
                # whole-shard verify failed but every leaf now verifies:
                # repaired between the two walks — treat as good
                corrupt_local.remove(sid)
                good_local.append(sid)
                report.corrupt_local.remove(sid)
                report.local_sources = sorted(
                    set(report.local_sources) | {sid}
                )
                continue
            wire = [0]

            def read_range(src: int, lo: int, size: int) -> bytes | None:
                if src in good_local:
                    try:
                        faults.fire(
                            "ec.repair.source_read", shard=src, offset=lo
                        )
                        with open(base + ctx.to_ext(src), "rb") as f:
                            f.seek(lo)
                            got = f.read(size)
                        if len(got) == size:
                            return faults.mutate(
                                "ec.repair.source_read", got,
                                shard=src, offset=lo,
                            )
                    except (OSError, IOError):
                        pass  # transient local I/O: the same shard may
                        # still be servable by a peer holder below —
                        # don't forfeit the cheap ranged path over it
                for peer in holders.get(src, []):
                    if peer in excluded:
                        continue
                    try:
                        return _fetch_range_verified(
                            peer, src, lo, size, prot, fetch, policy,
                            wire, sp,
                        )
                    except PeerCorruptError as e:
                        log.warning("excluding peer: %s", e)
                        trace.event(
                            sp, "peer_excluded", peer=peer, shard=src
                        )
                        excluded.add(peer)
                        continue
                    except (PeerFetchTransient, RetryError) as e:
                        log.warning(
                            "peer %s unreachable for shard %d range "
                            "[%d:+%d): %s", peer, src, lo, size, e,
                        )
                        continue
                return None

            candidates = sorted(good_local) + sorted(
                s for s in holders
                if s not in good_local and s != sid and 0 <= s < ctx.total
            )
            try:
                patches = reconstruct_leaves(
                    prot, ctx, sid, bad, read_range, candidates,
                    backend=backend, span=sp,
                )
                apply_leaf_repair(path, sid, prot, patches, span=sp)
            except (ECError, OSError) as e:
                M.ec_leaf_repairs_total.inc(outcome="failed")
                log.warning(
                    "ranged leaf repair of shard %d failed (%s); falling "
                    "back to whole-shard peer rebuild", sid, e,
                )
                continue
            corrupt_local.remove(sid)
            good_local.append(sid)
            report.corrupt_local.remove(sid)
            report.local_sources = sorted(set(report.local_sources) | {sid})
            report.leaf_repaired[sid] = sorted(bad)
            report.repair_wire_bytes += wire[0]
            report.patched_ranges[sid] = patched_byte_ranges(prot, sid, bad)
            M.ec_leaf_repairs_total.inc(outcome="repaired")
            log.warning(
                "leaf-repaired shard %d in place (leaves %s, %d wire "
                "bytes)", sid, sorted(bad), wire[0],
            )
        report.excluded_peers = sorted(excluded)

    if targets is None:
        want = sorted(set(range(ctx.total)) - set(good_local))
    else:
        # present-but-corrupt shards are always replaced, like the
        # local rebuild's verify-and-exclude contract
        want = sorted(set(targets) | set(corrupt_local))
        want = [i for i in want if i not in good_local]
    if not want:
        return report

    sdir = staging_dir(base)
    _clear_staging(sdir)  # leftovers from a crashed attempt
    os.makedirs(sdir, exist_ok=True)
    sbase = os.path.join(sdir, os.path.basename(base))

    # `excluded` carries over from the ranged-repair stage: a holder
    # that served rot for a 64 KiB range serves rot, full stop.
    unreachable: set[tuple[str, int]] = set()
    gsize = max(prot.verify_granularity(i)[0] for i in range(ctx.total))
    batch = max(DEFAULT_BATCH - DEFAULT_BATCH % gsize, gsize)
    # The pipeline's stages hang off an `ec.rebuild` child, as a local
    # rebuild's do; the wait for the peers' rows is the heal's own.
    rsp = trace.start(
        "ec.rebuild", name=os.path.basename(base), base=sbase,
        present=len(good_local), missing=list(want), priority=priority,
    )
    try:
        # ---- plan k verified sources, stream them through the chip ----
        # Local sources are read where they lie; the rest stream from
        # their holders into the batch matrix, never to disk. A holder
        # that fails a batch (rot, or a transport that outlived its
        # retries) is dropped from the plan and the heal starts over
        # with another: nothing is published before every batch landed.
        while True:
            local = sorted(good_local)[:k]
            candidates = sorted(
                sid
                for sid, peers in holders.items()
                if peers and sid not in good_local and sid not in want
                and 0 <= sid < ctx.total
            )
            plan = _plan_sources(
                k - len(local), candidates, holders, excluded, unreachable
            )
            report.excluded_peers = sorted(excluded)
            if plan is None:
                reachable = {
                    sid for sid in candidates
                    if any(p not in excluded and (p, sid) not in unreachable
                           for p in holders[sid])
                }
                raise ECError(
                    f"peer-fetch rebuild for {base}: only "
                    f"{len(local) + len(reachable)} verified source shards "
                    f"reachable (local {sorted(good_local)}, from peers "
                    f"{sorted(reachable)}, excluded peers "
                    f"{sorted(excluded)}); need {k} — refusing, nothing "
                    f"published"
                )
            sources = None
            if plan:
                sources = PeerSources(
                    plan, len(local), prot, fetch, fetch_into, policy, sp
                )
            try:
                bad = _attempt_rebuild(
                    sbase, ctx, backend, prot, local + sorted(plan), want,
                    prot.shard_sizes[local[0] if local else min(plan)],
                    batch, faults.active(),
                    inline_verify=not faults.active(),
                    priority=priority, scheduler=scheduler, span=rsp,
                    src_base=base, remote=sources,
                )
            except PeerSourceError as e:
                for sid, peer, cause in e.failures:
                    if isinstance(cause, PeerCorruptError):
                        # verify-and-exclude across the wire: this holder
                        # serves rot; nothing it sends is trustworthy
                        log.warning("excluding peer: %s", cause)
                        trace.event(sp, "peer_excluded", peer=peer, shard=sid)
                        excluded.add(peer)
                    else:
                        log.warning(
                            "peer %s unreachable for shard %d: %s",
                            peer, sid, cause,
                        )
                        unreachable.add((peer, sid))
                continue
            finally:
                if sources is not None:
                    sources.close()
            if bad:
                # confirmed on-disk rot in a local source: it is a
                # target now, and the plan goes without it
                for sid in bad:
                    good_local.remove(sid)
                    report.corrupt_local.append(sid)
                report.local_sources = sorted(good_local)
                want = sorted(set(want) | set(bad))
                continue
            break
        report.fetched = dict(plan)
        report.fetched_plane = dict(sources.planes) if sources else {}
        report.excluded_peers = sorted(excluded)
        if sp is not None:
            sp.count(
                "source_bytes_staged",
                _staged_source_bytes(sdir, sbase, ctx, want),
            )

        # Crash window: regenerated targets are durable in staging but
        # not yet at the canonical paths. A crash here (or between the
        # per-target renames below) republishes idempotently on re-run:
        # already-renamed targets verify good and drop out of `want`.
        faults.fire("ec.peer_rebuild.before_publish", base=base)
        for sid in want:
            os.replace(sbase + ctx.to_ext(sid), base + ctx.to_ext(sid))
            faults.fire("ec.peer_rebuild.after_publish", base=base, shard=sid)
            report.rebuilt.append(sid)
        fsync_dir(base + ".ecsum")
    finally:
        trace.finish(rsp)
        _clear_staging(sdir)
    return report
