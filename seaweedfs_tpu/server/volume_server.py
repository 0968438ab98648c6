"""Volume server: HTTP data plane + gRPC control/EC plane + heartbeats.

Reference: weed/server/volume_server.go, HTTP handlers
(volume_server_handlers_read.go:142 GetOrHeadHandler,
_write.go:20 PostHandler -> topology.ReplicatedWrite store_replicate.go:32),
gRPC EC RPCs (volume_grpc_erasure_coding.go), heartbeat stream
(volume_grpc_client_to_master.go).
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from concurrent import futures
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

import grpc
import numpy as np

from ..ec import context as ec_context
from ..ec import fleet
from ..ec.context import ECError
from ..ec.decoder import ec_decode_volume
from ..ec.encoder import ec_encode_volume
from ..ec.rebuild import rebuild_ec_files
from ..ec.volume_info import VolumeInfo
from ..storage.file_id import FileId, FileIdError
from ..storage.needle import Needle, NeedleError
from ..storage.store import Store
from ..storage.volume import (
    CookieMismatch,
    NotFoundError,
    ReadOnlyError,
    Volume,
    VolumeError,
)
from ..pb import cluster_pb2 as pb
from ..pb import rpc
from ..utils import metrics as M
from ..utils import request_id as _rid
from ..utils import trace
from ..utils.glog import logger

log = logger("volume")

_EC_STREAM_CHUNK = 256 * 1024


def _shard_bits(ids) -> int:
    bits = 0
    for i in ids:
        bits |= 1 << i
    return bits


class VolumeService:
    """gRPC servicer over one Store."""

    def __init__(self, server: "VolumeServer"):
        self.server = server
        self.store = server.store

    def _rpc_span(self, op: str, request, context, **attrs):
        """Server-side end of cross-RPC tracing for the EC RPCs: adopt
        the caller's X-Request-ID (minting one at chain start) and —
        when the flight recorder is armed — continue the caller's trace
        as a local root, so a fleet-dispatched rebuild and every peer
        shard-read it triggers share ONE trace id. Returns None when
        the tracer is disarmed; request-id adoption always runs (it is
        one contextvar set)."""
        md = trace.metadata_dict(context)
        _rid.ensure(md.get(trace.REQUEST_ID_KEY))
        return trace.start_from_metadata(
            op, md,
            server=f"{self.server.ip}:{self.server.port}",
            volume=request.volume_id,
            **attrs,
        )

    # ------------------------------------------------------------ admin

    def AllocateVolume(self, request, context):
        self.store.allocate_volume(
            request.volume_id,
            collection=request.collection,
            replica_placement=request.replication or "000",
            ttl=request.ttl,
            disk_type=request.disk_type,
        )
        self.server.notify_new_volume(request.volume_id)
        return pb.AllocateVolumeResponse()

    def VolumeDelete(self, request, context):
        try:
            self.store.delete_volume(request.volume_id)
            self.server.notify_deleted_volume(request.volume_id)
            return pb.VolumeCommandResponse()
        except NotFoundError as e:
            return pb.VolumeCommandResponse(error=str(e))

    def VolumeMount(self, request, context):
        """Load an existing .dat/.idx pair from disk into the store
        (used after VolumeCopy pulled the files from a peer)."""
        try:
            self.store.mount_volume(request.volume_id, request.collection)
        except NotFoundError as e:
            return pb.VolumeCommandResponse(error=str(e))
        self.server.notify_new_volume(request.volume_id)
        return pb.VolumeCommandResponse()

    def VolumeCopy(self, request, context):
        """Pull a whole volume (.dat + .idx + .vif) from a peer, then
        load it (reference VolumeCopy volume_grpc_copy.go). All files
        land as temps and publish together — a half-copied volume never
        becomes loadable."""
        if self.store.find_volume(request.volume_id) is not None:
            return pb.VolumeCommandResponse(error="volume already here")
        loc = self.store._pick_location()
        base = Volume.base_file_name(
            loc.directory, request.collection, request.volume_id
        )
        exts = (".dat", ".idx", ".vif")
        tmps: dict[str, str] = {}
        try:
            with grpc.insecure_channel(request.source_url) as ch:
                stub = rpc.volume_stub(ch)
                for ext in exts:
                    tmp = base + ext + ".copying"
                    try:
                        with open(tmp, "wb") as f:
                            for chunk in stub.CopyFile(
                                pb.CopyFileRequest(
                                    volume_id=request.volume_id,
                                    collection=request.collection,
                                    ext=ext,
                                )
                            ):
                                f.write(chunk.data)
                            f.flush()
                            os.fsync(f.fileno())
                        tmps[ext] = tmp
                    except grpc.RpcError as e:
                        if os.path.exists(tmp):
                            os.unlink(tmp)
                        if ext == ".vif":  # optional sidecar
                            continue
                        raise RuntimeError(
                            f"copy {ext}: {e.details()}"
                        ) from None
            for ext, tmp in tmps.items():
                os.replace(tmp, base + ext)
            tmps.clear()
        except RuntimeError as e:
            return pb.VolumeCommandResponse(error=str(e))
        finally:
            for tmp in tmps.values():
                if os.path.exists(tmp):
                    os.unlink(tmp)
        self.store.mount_volume(request.volume_id, request.collection)
        self.server.notify_new_volume(request.volume_id)
        return pb.VolumeCommandResponse()

    def VolumeUnmount(self, request, context):
        """Release the volume, keep its files (reference
        volume_grpc_admin.go VolumeUnmount)."""
        try:
            self.store.unmount_volume(request.volume_id)
        except NotFoundError as e:
            return pb.VolumeCommandResponse(error=str(e))
        self.server.notify_deleted_volume(request.volume_id)
        return pb.VolumeCommandResponse()

    def VolumeConfigure(self, request, context):
        """Rewrite replica placement in place (reference
        VolumeConfigure); the next heartbeat reports the new value."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeCommandResponse(error="volume not found")
        try:
            v.set_replica_placement(request.replication)
        except (ValueError, VolumeError) as e:
            return pb.VolumeCommandResponse(error=str(e))
        self.server.notify_new_volume(request.volume_id)
        return pb.VolumeCommandResponse()

    def VolumeMarkReadonly(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeCommandResponse(error="not found")
        v.set_read_only(True)
        return pb.VolumeCommandResponse()

    def VolumeMarkWritable(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeCommandResponse(error="not found")
        v.set_read_only(False)
        return pb.VolumeCommandResponse()

    def VacuumVolume(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        ratio = v.garbage_ratio()
        if request.garbage_threshold and ratio < request.garbage_threshold:
            return pb.VacuumResponse(reclaimed_bytes=0, garbage_ratio=ratio)
        reclaimed = v.vacuum()
        return pb.VacuumResponse(reclaimed_bytes=reclaimed, garbage_ratio=ratio)

    # --------------------------------------------------------------- io

    def _grpc_jwt_ok(self, context, vid: int, needle_id: int) -> bool:
        """gRPC writes must not bypass the HTTP JWT gate: when the
        cluster has a key, peer callers attach a self-signed token in
        metadata. context None = internal call from the already-verified
        HTTP handler."""
        if not self.server.jwt_key or context is None:
            return True
        from ..storage.file_id import FileId
        from ..utils.security import JwtError, verify_jwt

        token = ""
        for k, v in context.invocation_metadata():
            if k == "authorization":
                token = v[7:] if v.startswith("Bearer ") else v
        try:
            # needle-scoped tokens carry a cookie we don't know here;
            # accept volume-scoped tokens (what peers sign)
            verify_jwt(self.server.jwt_key, token, str(vid))
            return True
        except JwtError:
            return False

    def WriteNeedle(self, request, context):
        if not self._grpc_jwt_ok(context, request.volume_id, request.needle_id):
            return pb.WriteNeedleResponse(error="unauthorized")
        with M.request_seconds.time(server="volume", op="write"):
            resp = self._write_needle(request)
        M.request_total.inc(
            server="volume", op="write", code="err" if resp.error else "ok"
        )
        return resp

    def _write_needle(self, request):
        n = Needle(
            cookie=request.cookie,
            needle_id=request.needle_id,
            data=request.data,
            flags=request.flags,
        )
        if request.name:
            n.set_name(request.name.encode())
        if request.mime:
            n.set_mime(request.mime.encode())
        try:
            size = self.store.write_needle(request.volume_id, n)
        except (NotFoundError, ReadOnlyError, VolumeError, ValueError, OSError) as e:
            return pb.WriteNeedleResponse(error=str(e))
        if not request.is_replicate:
            err = self.server.replicate_write(request)
            if err:
                return pb.WriteNeedleResponse(error=err)
        return pb.WriteNeedleResponse(size=size)

    def ReadNeedle(self, request, context):
        with M.request_seconds.time(server="volume", op="read"):
            resp = self._read_needle(request)
        M.request_total.inc(
            server="volume", op="read", code="err" if resp.error else "ok"
        )
        return resp

    def _read_needle(self, request):
        try:
            n = self.store.read_needle(
                request.volume_id,
                request.needle_id,
                request.cookie or None,
            )
        except (NotFoundError, ECError) as e:
            return pb.ReadNeedleResponse(error=f"not found: {e}")
        except (CookieMismatch, NeedleError, VolumeError, ValueError, OSError) as e:
            return pb.ReadNeedleResponse(error=str(e))
        return pb.ReadNeedleResponse(
            data=n.data,
            name=n.name.decode(errors="replace"),
            mime=n.mime.decode(errors="replace"),
            last_modified=n.last_modified,
        )

    def DeleteNeedle(self, request, context):
        if not self._grpc_jwt_ok(context, request.volume_id, request.needle_id):
            return pb.DeleteNeedleResponse(error="unauthorized")
        try:
            freed = self.store.delete_needle(request.volume_id, request.needle_id)
        except NotFoundError as e:
            return pb.DeleteNeedleResponse(error=str(e))
        except (ECError, VolumeError, ValueError, OSError) as e:
            # a volume mid-conversion/close must yield an error RESPONSE,
            # never an escaped exception that aborts the connection
            return pb.DeleteNeedleResponse(error=f"volume busy: {e}")
        if not request.is_replicate:
            ev = self.store.find_ec_volume(request.volume_id)
            if ev is not None:
                # EC tombstones must reach every shard holder's .ecj
                # (reference ec_volume_delete distribution), or a later
                # decode/serve from another holder resurrects the blob
                err = self.server.replicate_ec_delete(
                    request.volume_id, ev.collection, request.needle_id
                )
                if err:
                    return pb.DeleteNeedleResponse(
                        freed_bytes=freed, error=err
                    )
            else:
                self.server.replicate_delete(request)
        return pb.DeleteNeedleResponse(freed_bytes=freed)

    # ---------------------------------------------------------------- ec

    def VolumeEcShardsGenerate(self, request, context):
        """Reference volume_grpc_erasure_coding.go:45 — wipe stale EC
        artifacts, mark the volume readonly, encode (ecx first), persist
        sidecars."""
        sp = self._rpc_span("rpc.ec_shards_generate", request, context)
        try:
            with trace.activate(sp):
                return self._ec_shards_generate(request, context)
        finally:
            trace.finish(sp)

    def _ec_shards_generate(self, request, context):
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(grpc.StatusCode.NOT_FOUND, "volume not found")
        if request.collection and v.collection != request.collection:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, "collection mismatch")
        base = v.dat_path[:-4]
        for i in range(ec_context.MAX_SHARD_COUNT):
            stale = base + f".ec{i:02d}"
            if os.path.exists(stale):
                os.unlink(stale)
        v.set_read_only(True)
        v.flush()
        ctx = ec_context.ECContext(
            request.data_shards or ec_context.DATA_SHARDS,
            request.parity_shards or ec_context.PARITY_SHARDS,
        )
        from ..ec.backend import get_backend

        backend = get_backend(
            request.backend or self.server.store.ec_backend,
            ctx.data_shards,
            ctx.parity_shards,
        )
        backend_name = request.backend or self.server.store.ec_backend
        dat_size = os.path.getsize(base + ".dat")
        from ..ec.encoder import DEFAULT_BATCH

        batch = (request.batch_mb << 20) if request.batch_mb else DEFAULT_BATCH
        with M.request_seconds.time(server="volume", op="ec_encode"):
            vi = ec_encode_volume(
                base, ctx, backend, batch_size=batch,
                scheduler=self.store.ec_scheduler,
            )
        M.ec_ops_total.inc(op="encode", backend=backend_name)
        M.ec_bytes_total.inc(dat_size, op="encode", backend=backend_name)
        return pb.EcShardsGenerateResponse(generation=vi.encode_ts_ns)

    def VolumeEcShardsRebuild(self, request, context):
        sp = self._rpc_span(
            "rpc.ec_shards_rebuild", request, context,
            from_peers=bool(request.from_peers),
        )
        try:
            with trace.activate(sp):
                return self._ec_shards_rebuild(request, context)
        finally:
            trace.finish(sp)

    def _ec_shards_rebuild(self, request, context):
        loc_base = self._ec_base(request.volume_id, request.collection)
        if loc_base is None:
            context.abort(grpc.StatusCode.NOT_FOUND, "ec volume not found")
        if request.from_peers:
            # Cluster-level rebuild: a subset holder (< k local shards)
            # streams sibling shards from peer holders, rebuilds on the
            # local device, and distributes regenerated cluster-lost
            # shards to planned holders (server.peer_fetch_rebuild).
            try:
                out = self.server.peer_fetch_rebuild(
                    request.volume_id,
                    collection=request.collection,
                    backend_name=request.backend,
                )
            except ECError as e:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
            return pb.EcShardsRebuildResponse(
                rebuilt_shard_ids=out["rebuilt"],
                fetched_shard_ids=out["fetched"],
                distributed_shard_ids=out["distributed"],
                repaired_shard_ids=out["repaired"],
            )
        from ..ec.backend import get_backend
        from ..ec.volume_info import VolumeInfo

        vi = VolumeInfo.maybe_load(loc_base + ".vif")
        ctx = (vi.ec_ctx if vi else None) or ec_context.ECContext()
        backend = get_backend(
            request.backend or self.server.store.ec_backend,
            ctx.data_shards,
            ctx.parity_shards,
        )
        # Regenerate absent shards only within this server's legitimate
        # set (mounted + quarantined) PLUS shards the master knows no
        # location for (lost cluster-wide — ec.rebuild's restore-
        # redundancy contract). A shard absent here but alive on a peer
        # is excluded: minting a local copy would create a duplicate
        # the master never placed. Present-but-corrupt shards are
        # always replaced. An unmounted volume (offline repair) or an
        # unreachable master keeps the unrestricted file-level behavior.
        ev = self.store.find_ec_volume(request.volume_id)
        only = None
        if ev is not None:
            try:
                located = self.server._master_client().lookup_ec(
                    request.volume_id, refresh=True
                )
                lost = {
                    sid
                    for sid in range(ctx.total)
                    if not located.get(sid)
                }
            except Exception:
                lost = set(range(ctx.total))  # no topology: old behavior
            only = sorted(set(ev.legitimate_shards()) | lost)
        try:
            with M.request_seconds.time(server="volume", op="ec_rebuild"):
                rebuilt = rebuild_ec_files(
                    loc_base, backend=backend, only_shards=only,
                    scheduler=self.store.ec_scheduler,
                )
        except ECError as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        M.ec_ops_total.inc(
            op="rebuild", backend=request.backend or self.server.store.ec_backend
        )
        # swap a mounted volume's fds onto the regenerated inodes — the
        # pre-rename fds still read the old (possibly corrupt) bytes
        # (quarantined shards re-enter service here too)
        if ev is not None and rebuilt:
            ev.reopen_shards(rebuilt)
        return pb.EcShardsRebuildResponse(rebuilt_shard_ids=rebuilt)

    def VolumeEcShardsCopy(self, request, context):
        """Pull shards (and index files) from a peer.

        Metadata files (.ecx/.ecj/.vif/.ecsum) land FIRST over the
        gRPC CopyFile stream, so the generation fence and the bitrot
        sidecar exist locally before any shard byte moves. Shard files
        then prefer the source's native shard plane
        (ec/net_plane.ShardNetPlane: sendfile egress, generation-fenced
        by the .vif's encode_ts_ns, bytes attributed
        plane=native) with CopyFile as the bit-identical fallback —
        this is the byte path `ec.balance` moves and `ec_migrate`
        hot-volume migrations ride. Every landed shard is verified
        against the local .ecsum sidecar when one covers this
        generation: a mismatch unlinks the file and aborts the copy
        (DATA_LOSS) — a migration can never mount rot."""
        _rid.ensure(trace.metadata_dict(context).get(trace.REQUEST_ID_KEY))
        loc = self.store._pick_location()
        base = Volume.base_file_name(
            loc.directory, request.collection, request.volume_id
        )
        meta_exts = []
        if request.copy_ecx:
            meta_exts.append(".ecx")
        if request.copy_ecj:
            meta_exts.append(".ecj")
        if request.copy_vif:
            meta_exts.append(".vif")
        if request.copy_ecsum:
            meta_exts.append(".ecsum")
        with grpc.insecure_channel(request.source_url) as ch:
            stub = rpc.volume_stub(ch)

            def copy_file(ext: str) -> None:
                tmp = base + ext + ".copying"
                try:
                    with open(tmp, "wb") as f:
                        for chunk in stub.CopyFile(
                            pb.CopyFileRequest(
                                volume_id=request.volume_id,
                                collection=request.collection,
                                ext=ext,
                            ),
                            metadata=trace.grpc_metadata(),
                        ):
                            f.write(chunk.data)
                        f.flush()
                        os.fsync(f.fileno())
                    os.replace(tmp, base + ext)
                except grpc.RpcError as e:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
                    if ext == ".ecj":  # journal may legitimately not exist
                        return
                    context.abort(
                        grpc.StatusCode.UNAVAILABLE,
                        f"copy {ext}: {e.details()}",
                    )

            for ext in meta_exts:
                copy_file(ext)
            # Generation fence + sidecar, from whatever .vif/.ecsum is
            # now local (just copied, or already here from an earlier
            # shard of this volume).
            generation = 0
            vi = VolumeInfo.maybe_load(base + ".vif")
            if vi is not None:
                generation = vi.encode_ts_ns
            prot = None
            try:
                from ..ec.bitrot import BitrotProtection

                prot = BitrotProtection.load(base + ".ecsum")
                if generation and prot.generation not in (0, generation):
                    prot = None  # stale sidecar: no ground truth
            except Exception:  # absent/unreadable: verification off
                prot = None
            for sid in request.shard_ids:
                ext = f".ec{sid:02d}"
                if not self._copy_shard_native(
                    request, base, ext, sid, generation
                ):
                    copy_file(ext)
                if prot is not None and 0 <= sid < len(prot.shard_crcs):
                    bad = prot.verify_shard_file(
                        base + ext, sid, stop_early=True
                    )
                    if bad:
                        os.unlink(base + ext)
                        context.abort(
                            grpc.StatusCode.DATA_LOSS,
                            f"shard {sid} from {request.source_url} "
                            f"fails .ecsum verification; copy refused",
                        )
        return pb.EcShardsCopyResponse()

    def _copy_shard_native(
        self, request, base: str, ext: str, sid: int, generation: int
    ) -> bool:
        """Try to land one shard file over the source's shard net
        plane (sendfile -> pooled buffer -> local file, atomic
        replace). False = caller takes the gRPC CopyFile path (plane
        disabled, armed faults, peer without a sidecar, refusal)."""
        from .. import faults
        from ..ec import native_io
        from ..ec import net_plane as _netp

        if not native_io.enabled() or faults.active():
            return False
        tmp = base + ext + ".copying"
        try:
            client = self.server._net_plane_client()
            with open(tmp, "wb") as f:
                n = client.fetch_shard_to_file(
                    _netp.net_addr(request.source_url),
                    request.volume_id, sid, generation, f,
                )
                f.flush()
                os.fsync(f.fileno())
            if n <= 0:
                os.unlink(tmp)
                return False
            os.replace(tmp, base + ext)
            return True
        except (_netp.NetPlaneError, _netp.NetPlaneUnavailable, OSError):
            try:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            except OSError:
                pass
            return False

    def VolumeEcShardsDelete(self, request, context):
        for loc in self.store.locations:
            base = Volume.base_file_name(
                loc.directory, request.collection, request.volume_id
            )
            for sid in request.shard_ids:
                p = base + f".ec{sid:02d}"
                if os.path.exists(p):
                    os.unlink(p)
            # drop index files when no shards remain anywhere local
            if not any(
                os.path.exists(base + f".ec{i:02d}")
                for i in range(ec_context.MAX_SHARD_COUNT)
            ):
                for ext in (".ecx", ".ecj", ".ecsum", ".heat"):
                    if os.path.exists(base + ext):
                        os.unlink(base + ext)
        self.server.notify_deleted_ec_shards(
            request.volume_id, request.collection, list(request.shard_ids)
        )
        return pb.EcShardsDeleteResponse()

    def VolumeEcShardsMount(self, request, context):
        try:
            ev = self.store.mount_ec_volume(request.volume_id, request.collection)
        except NotFoundError as e:
            context.abort(grpc.StatusCode.NOT_FOUND, str(e))
        self.server.notify_new_ec_shards(request.volume_id, request.collection)
        return pb.EcShardsMountResponse()

    def VolumeEcShardsUnmount(self, request, context):
        self.store.unmount_ec_shards(request.volume_id, list(request.shard_ids))
        return pb.EcShardsUnmountResponse()

    def VolumeEcShardRead(self, request, context):
        from .. import faults

        # Streaming RPC: the span covers the whole response stream (the
        # "stream" stage includes time blocked on a slow consumer) and,
        # because the trace id arrives in metadata, a peer-fetch
        # rebuild's every shard-read stream lands in the DISPATCHER's
        # trace — one id from master task to this peer.
        sp = self._rpc_span(
            "rpc.ec_shard_read", request, context,
            shard=request.shard_id, offset=request.offset,
            size=request.size,
        )
        # `stream` and the two parts this servicer has (`.resolve`, and
        # the pread/yield loop as `.sendfile`) from clock readings: a
        # generator cannot hold a with-scoped stage across its yields
        t0_ns = resolved_ns = time.perf_counter_ns() if sp is not None else 0
        try:
            ev = self.store.find_ec_volume(request.volume_id)
            if ev is None:
                context.abort(grpc.StatusCode.NOT_FOUND, "ec volume not mounted")
            if request.generation and ev.encode_ts_ns != request.generation:
                # generation fence (reference store_ec.go:627)
                context.abort(grpc.StatusCode.FAILED_PRECONDITION, "stale generation")
            fd = ev.shard_fds.get(request.shard_id)
            if fd is None:
                context.abort(grpc.StatusCode.NOT_FOUND, "shard not local")
            try:
                # Named point for peer-read chaos: a raised IOError aborts
                # the stream (client falls back to other peers/recovery); a
                # mutate tears or corrupts the streamed bytes, which the
                # CLIENT must catch (short-read check / needle CRC /
                # sidecar-verified reconstruction) — never serve silently.
                faults.fire(
                    "server.ec_shard_read",
                    volume=request.volume_id, shard=request.shard_id,
                )
            except IOError as e:
                context.abort(grpc.StatusCode.UNAVAILABLE, str(e))
            if sp is not None:
                resolved_ns = time.perf_counter_ns()
            remaining = request.size
            off = request.offset
            while remaining > 0:
                # The Python-plane stream: every chunk is materialized
                # as bytes for the protobuf message (counted against
                # bytes_copied_per_byte_served). The native twin of
                # this loop is ec/net_plane.ShardNetPlane, which
                # sendfile(2)s the same fd range with zero Python-side
                # byte handling — `_PeerShardReader` and the peer rebuild
                # prefer it and fall back here.
                chunk = os.pread(fd, min(_EC_STREAM_CHUNK, remaining), off)
                if not chunk:
                    break
                orig_len = len(chunk)
                M.net_bytes_copied_total.inc(orig_len, plane="python", direction="read")
                chunk = faults.mutate(
                    "server.ec_shard_read", chunk,
                    volume=request.volume_id, shard=request.shard_id, offset=off,
                )
                if chunk:
                    yield pb.EcShardReadChunk(data=chunk)
                    M.net_bytes_sent_total.inc(len(chunk), plane="python", direction="read")
                if len(chunk) < orig_len:
                    break  # torn stream: client sees a short read
                off += orig_len
                remaining -= orig_len
        finally:
            if sp is not None:
                end_ns = time.perf_counter_ns()
                if resolved_ns > t0_ns:  # not refused before the first chunk
                    sp.add_interval("stream.resolve", t0_ns, resolved_ns)
                    sp.add_interval("stream.sendfile", resolved_ns, end_ns)
                sp.add_interval("stream", t0_ns, end_ns)
                sp.finish(end_ns)

    def VolumeEcBlobDelete(self, request, context):
        # a mutation: on keyed clusters it needs the same peer token the
        # gRPC write path demands (fan-out attaches it)
        if not self._grpc_jwt_ok(context, request.volume_id, request.needle_id):
            context.abort(grpc.StatusCode.PERMISSION_DENIED, "unauthorized")
        ev = self.store.find_ec_volume(request.volume_id)
        if ev is None:
            context.abort(grpc.StatusCode.NOT_FOUND, "ec volume not mounted")
        ev.delete_needle(request.needle_id)
        return pb.EcBlobDeleteResponse()

    def VolumeEcShardsToVolume(self, request, context):
        sp = self._rpc_span("rpc.ec_shards_to_volume", request, context)
        try:
            with trace.activate(sp):
                return self._ec_shards_to_volume(request, context)
        finally:
            trace.finish(sp)

    def _ec_shards_to_volume(self, request, context):
        base = self._ec_base(request.volume_id, request.collection)
        if base is None:
            context.abort(grpc.StatusCode.NOT_FOUND, "ec volume not found")
        self.store.unmount_ec_volume(request.volume_id)
        try:
            ec_decode_volume(base, scheduler=self.store.ec_scheduler)
        except ECError as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        # register the decoded normal volume
        self.store.mount_volume(request.volume_id, request.collection)
        self.server.notify_new_volume(request.volume_id)
        return pb.EcShardsToVolumeResponse()

    def CopyFile(self, request, context):
        """Stream a volume/EC file, optionally from start_offset — the
        tail form backs incremental remote backup (reference
        VolumeTailSender / VolumeIncrementalCopy)."""
        base = self._ec_base(request.volume_id, request.collection, require=False)
        path = (base or "") + request.ext
        if base is None or not os.path.exists(path):
            context.abort(grpc.StatusCode.NOT_FOUND, f"no {request.ext}")
        v = self.store.find_volume(request.volume_id)
        if v is not None and request.ext in (".dat", ".idx"):
            v.flush()  # a tail read must see every acknowledged write
        stop = request.stop_offset or os.path.getsize(path)
        with open(path, "rb") as f:
            sent = request.start_offset
            f.seek(sent)
            while sent < stop:
                chunk = f.read(min(_EC_STREAM_CHUNK, stop - sent))
                if not chunk:
                    break
                yield pb.CopyFileChunk(data=chunk)
                sent += len(chunk)

    def VolumeTierUpload(self, request, context):
        """Move a sealed volume's .dat to the cold tier (reference
        volume_grpc_tier_upload.go); .idx stays local so lookups never
        touch the backend."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.TierResponse(error="volume not found")
        try:
            moved = v.tier_upload(request.dest_url, keep_local=request.keep_local)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            return pb.TierResponse(error=str(e))
        return pb.TierResponse(moved_bytes=moved)

    def VolumeTierDownload(self, request, context):
        """Bring a cold-tiered .dat back onto local disk (reference
        volume_grpc_tier_download.go)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.TierResponse(error="volume not found")
        try:
            moved = v.tier_download(delete_remote=request.delete_remote)
        except Exception as e:  # noqa: BLE001
            return pb.TierResponse(error=str(e))
        return pb.TierResponse(moved_bytes=moved)

    # ---------------------------------------- tail / incremental sync
    # Reference: weed/server/volume_grpc_tail.go (VolumeTailSender /
    # VolumeTailReceiver) and weed/storage/volume_backup.go
    # (VolumeIncrementalCopy) — replica catch-up after downtime pulls
    # only the records appended since the replica's own appendAtNs.

    _TAIL_POLL_S = 0.25  # follow-loop poll (ref uses 2s; tests want fast)

    def VolumeTailSender(self, request, context):
        """Stream needle records appended after since_ns; keep following
        until no new appends for idle_timeout_seconds (0 = forever)."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"volume {request.volume_id} not found",
            )
        try:
            v._require_v3()  # v2 has no appendAtNs: refuse, never
            #                  stream garbage-timestamped silence
            # position once (idx binary search); every later poll just
            # compares the cached .dat position against the append end
            # — O(1) while idle, no idx re-reads
            pos = v._walk_start_for(request.since_ns)
        except VolumeError as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        draining = float(request.idle_timeout_seconds)
        while True:
            end = v._append_end()
            progressed = False
            if pos < end:
                for _n, raw, ts in v.scan_records_between(pos, end):
                    if ts <= request.since_ns:
                        continue  # first segment may start at an older put
                    header, rest = raw[:16], raw[16:]
                    first = True
                    for i in range(0, max(len(rest), 1), _EC_STREAM_CHUNK):
                        yield pb.VolumeTailChunk(
                            needle_header=header if first else b"",
                            needle_body=rest[i : i + _EC_STREAM_CHUNK],
                            version=v.version,
                        )
                        first = False
                    progressed = True
                pos = end
            # heartbeat: flushes the client's pending needle and keeps
            # the connection provably alive while idle
            yield pb.VolumeTailChunk(is_last_chunk=True, version=v.version)
            if request.idle_timeout_seconds == 0:
                time.sleep(self._TAIL_POLL_S)
                continue
            if progressed:
                draining = float(request.idle_timeout_seconds)
            else:
                draining -= self._TAIL_POLL_S
                if draining <= 0:
                    return
            time.sleep(self._TAIL_POLL_S)

    def VolumeTailReceiver(self, request, context):
        """Pull the tail FROM a source server into the local replica
        (server-side of `volume.sync`). since_ns=0 derives the resume
        point from the local volume's own last appendAtNs."""
        from ..client.volume_sync import tail_volume

        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeTailReceiverResponse(
                error=f"volume {request.volume_id} not found"
            )
        since = request.since_ns or v.last_append_at_ns()
        count = 0
        try:
            for n in tail_volume(
                request.source_volume_server,
                request.volume_id,
                since,
                request.idle_timeout_seconds or 3,
            ):
                if n.is_tombstone or (
                    not n.data and not n.flags and n.cookie == 0
                ):
                    # propagate the SOURCE's tombstone bytes verbatim.
                    # The 0x40 flag marks new-format tombstones; the
                    # legacy marker this codebase ever wrote is exactly
                    # Needle(cookie=0, data=b'') — an empty-body put
                    # with a NONZERO cookie is legitimate data and must
                    # replicate as a put, not a delete.
                    v.delete_needle(n.needle_id, tombstone=n)
                else:
                    v.write_needle(n)  # append_at_ns preserved -> same bytes
                count += 1
        except Exception as e:  # noqa: BLE001
            return pb.VolumeTailReceiverResponse(received=count, error=str(e))
        return pb.VolumeTailReceiverResponse(received=count)

    def VolumeIncrementalCopy(self, request, context):
        """Raw .dat bytes from the first record newer than since_ns to
        the current append point. First chunk carries start_offset so a
        byte-prefix follower (weed backup analog) can verify alignment
        before appending."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            context.abort(
                grpc.StatusCode.NOT_FOUND,
                f"volume {request.volume_id} not found",
            )
        try:
            off = v.offset_after_ns(request.since_ns)
        except VolumeError as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, str(e))
        end = v._append_end()
        if off >= end:
            yield pb.VolumeIncrementalCopyChunk(
                has_start=True, start_offset=end
            )
            return
        first = True
        with open(v.dat_path, "rb") as f:
            f.seek(off)
            sent = off
            while sent < end:
                data = f.read(min(_EC_STREAM_CHUNK, end - sent))
                if not data:
                    break
                yield pb.VolumeIncrementalCopyChunk(
                    file_content=data,
                    start_offset=off if first else 0,
                    has_start=first,
                )
                first = False
                sent += len(data)

    def ReadVolumeFileStatus(self, request, context):
        """Size/revision/version/lastAppendAtNs of a volume's files
        (reference volume_grpc_admin.go ReadVolumeFileStatus) — the
        handshake half of incremental backup."""
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.VolumeFileStatusResponse(error="volume not found")
        v.flush()
        try:
            last_ns = v.last_append_at_ns()
        except VolumeError:
            last_ns = 0  # v2 volume: no appendAtNs footer
        return pb.VolumeFileStatusResponse(
            dat_size=os.path.getsize(v.dat_path),
            idx_size=os.path.getsize(v.idx_path),
            compaction_revision=v.super_block.compaction_revision,
            version=v.version,
            last_append_at_ns=last_ns,
            collection=v.collection,
        )

    def ScrubVolume(self, request, context):
        """CRC-verify every live needle (reference volume_grpc_scrub.go).
        Reads go through the lock-free scan of the sealed portion; the
        volume stays online."""
        # task RPC: adopt the dispatcher's request id so this holder's
        # scrub log lines correlate with the fleet task that drove them
        _rid.ensure(trace.metadata_dict(context).get(trace.REQUEST_ID_KEY))
        v = self.store.find_volume(request.volume_id)
        if v is None:
            return pb.ScrubResponse(error="volume not found")
        v.flush()
        checked = 0
        bad: list[int] = []
        try:  # native mmap scanner (~3x the Python walk)
            from ..utils import native

            ids, offs, sizes, ok = native.scan_dat(v.dat_path)
            # iterate the arrays directly: no boxed-list copies of a
            # potentially many-million-record volume
            records = (
                (int(a), int(b), int(c), bool(d))
                for a, b, c, d in zip(ids, offs, sizes, ok)
            )
        except Exception:  # .so missing AND unbuildable included
            records = None
        if records is None:
            from ..storage.volume_scan import scan_volume_file

            _, items = scan_volume_file(v.dat_path)
            records = (
                (i.needle.needle_id, i.offset // 8, i.body_size, i.crc_ok)
                for i in items
            )
        for nid, stored_off, body_size, crc_ok in records:
            if body_size <= 0:
                continue
            nv = v.needle_map.get(nid)
            if nv is None or nv.is_deleted:
                continue  # dead record, vacuum's problem
            if nv.offset != stored_off:
                continue  # superseded copy; the live one is elsewhere
            checked += 1
            if not crc_ok:
                bad.append(nid)
        return pb.ScrubResponse(checked=checked, bad_needles=bad)

    def ScrubEcVolume(self, request, context):
        """Verify local shards against the .ecsum bitrot sidecar
        (reference ec_volume_scrub.go / store_ec_scrub.go)."""
        sp = self._rpc_span("rpc.scrub_ec_volume", request, context)
        try:
            with trace.activate(sp):
                return self._scrub_ec_volume(request, context)
        finally:
            trace.finish(sp)

    def _scrub_ec_volume(self, request, context):
        base = self._ec_base(request.volume_id, request.collection)
        if base is None:
            return pb.ScrubResponse(error="ec volume not found")
        from ..ec.bitrot import BitrotError, BitrotProtection

        if not os.path.exists(base + ".ecsum"):
            return pb.ScrubResponse(error="no bitrot sidecar")
        try:
            prot = BitrotProtection.load(base + ".ecsum")
        except BitrotError as e:
            return pb.ScrubResponse(error=f"sidecar unreadable: {e}")
        # Crash recovery BEFORE verification: replay (or roll back) any
        # pending <shard>.repair journal so this pass judges fully-old
        # or fully-new bytes, never a half-applied leaf patch — the
        # fleet scrub's recovery hook for holders with no local daemon.
        from ..ec.repair_journal import (
            patched_byte_ranges,
            recover_volume_journals,
        )

        rec = recover_volume_journals(base, prot.ctx, prot)
        journal_recovered = len(rec["replayed"]) + len(rec["rolled_back"])
        if rec["replayed"]:
            ev = self.store.find_ec_volume(request.volume_id)
            if ev is not None and prot.has_leaves:
                # in-place patches keep the inode: no fd swap, but any
                # cached reconstruction over the patched bytes is stale
                for sid, leaves in rec["replayed"].items():
                    ev.invalidate_shard_ranges(
                        sid, patched_byte_ranges(prot, sid, leaves)
                    )
        checked: list[int] = []
        bad: list[int] = []
        for i in range(prot.ctx.total):
            p = base + prot.ctx.to_ext(i)
            if not os.path.exists(p):
                continue
            checked.append(i)
            try:
                if prot.verify_shard_file(p, i):
                    bad.append(i)
            except OSError:
                bad.append(i)
        # checked_shards lets the shell do a real per-sid set difference
        # against the master's advertised placement; the bare count can
        # be masked by non-advertised local shard files. Quarantined
        # shards (renamed .bad, unmounted, so never "advertised") ride
        # along — the fleet scrub loop needs them to spot a holder that
        # is quarantined-but-unrebuildable and route a peer-fetch
        # rebuild at it. A quarantine whose canonical shard is back on
        # disk and verified good THIS pass is healed, not hurt: the
        # .bad file stays for forensics (bad_retention_s ages it out),
        # but reporting it would have the fleet loop dispatch a no-op
        # rebuild at this holder every scrub period forever.
        healed = set(checked) - set(bad)
        quarantined = [
            i
            for i in range(prot.ctx.total)
            if i not in healed
            and os.path.exists(
                base + prot.ctx.to_ext(i) + ec_context.QUARANTINE_SUFFIX
            )
        ]
        return pb.ScrubResponse(
            checked=len(checked),
            bad_shards=bad,
            checked_shards=checked,
            quarantined_shards=quarantined,
            repair_journal_recovered=journal_recovered,
        )

    def VolumeServerStatus(self, request, context):
        st = self.store.status()
        return pb.VolumeServerStatusResponse(
            volumes=[
                pb.VolumeInfoMsg(
                    id=v["id"],
                    collection=v["collection"],
                    size=v["size"],
                    file_count=v["file_count"],
                    deleted_count=v["deleted_count"],
                    deleted_bytes=v["deleted_bytes"],
                    read_only=v["read_only"],
                    replica_placement=v["replica_placement"],
                    version=v["version"],
                    ttl=v.get("ttl", ""),
                    disk_type=v.get("disk_type", "hdd"),
                )
                for v in st["volumes"]
            ],
            ec_shards=[
                pb.EcShardInfoMsg(
                    id=e["id"],
                    collection=e["collection"],
                    shard_bits=_shard_bits(e["shards"]),
                    shard_size=e["shard_size"],
                    data_shards=e["data_shards"],
                    parity_shards=e["parity_shards"],
                    generation=e["generation"],
                )
                for e in st["ec_volumes"]
            ],
        )

    # ------------------------------------------------------------ helpers

    def _ec_base(self, vid: int, collection: str, require: bool = True):
        """Directory base for a volume's EC artifacts on this server."""
        for loc in self.store.locations:
            base = Volume.base_file_name(loc.directory, collection, vid)
            if (
                os.path.exists(base + ".ecx")
                or os.path.exists(base + ".dat")
                or any(
                    os.path.exists(base + f".ec{i:02d}")
                    for i in range(ec_context.MAX_SHARD_COUNT)
                )
            ):
                return base
        return None


class _PeerShardReader:
    """`EcVolume.remote_reader` of one EC volume on a volume server:
    ranges of shards that lie elsewhere, from the peers the master lists
    for them, generation-fenced at the holder (reference
    store_ec.go:599-651). A range crosses the peer's native shard plane
    (`ec/net_plane.py`: `sendfile` there, received here into the buffer
    where it is used) and, where that plane does not answer, the
    `VolumeEcShardRead` stream."""

    def __init__(self, server: "VolumeServer", vid: int):
        self.server = server
        self.vid = vid

    def peers(self, shard_id: int) -> list[str]:
        """gRPC addresses of the OTHER servers the master lists for the
        shard, from the client's cached map: none where it lists none,
        so a caller can tell a read that asks a peer from a look-up
        that asks nobody."""
        vs = self.server
        try:
            locs = vs._master_client().lookup_ec(self.vid).get(shard_id, [])
        except (LookupError, grpc.RpcError):
            return []
        me = f"{vs.ip}:{vs.grpc_port}"
        addrs = (f"{loc.url.split(':')[0]}:{loc.grpc_port}" for loc in locs)
        return [peer for peer in addrs if peer != me]

    def read_into(
        self, shard_id: int, offset: int, size: int, generation: int,
        dst: np.ndarray, granule: int = 0,
    ):
        """Land [offset, offset+size) of the shard in `dst` (1-D uint8)
        from the first peer that answers in full. -> ("native", the
        granule CRCs rolled while the bytes landed; None without
        `granule`) where the peer's shard plane carried them, ("stream",
        None) where its `VolumeEcShardRead` did, None where no peer
        answered. Which of the two is chosen by what the connection
        shows: a plane that refuses the connect (memoized for 30 s by
        the client), refuses the request (stale generation, shard gone)
        or leaves the range short or torn sends the read to that peer's
        stream, then to the next peer."""
        from ..ec import net_plane as _netp

        client = self.server._net_plane_client()
        read_span = trace.current()  # armed: the caller's `ec.peer_read`
        if read_span is not None and read_span.op != "ec.peer_read":
            read_span = None  # a bare call under some other span
        for peer in self.peers(shard_id):
            if read_span is not None:
                read_span.attrs["peer"] = peer  # the address asked last
            try:
                crcs = client.read_into(
                    _netp.net_addr(peer), self.vid, shard_id, generation,
                    offset, size, dst, granule=granule,
                )
                return "native", crcs
            except (_netp.NetPlaneUnavailable, _netp.NetPlaneError):
                pass
            if self._stream_into(peer, shard_id, offset, size, generation, dst):
                return "stream", None
        return None

    def _stream_into(
        self, peer: str, shard_id: int, offset: int, size: int,
        generation: int, dst: np.ndarray,
    ) -> bool:
        """The same range over `peer`'s `VolumeEcShardRead`, chunk by
        chunk into `dst`; whether it came in full. Of the read's
        `ec.peer_read` span: `request_rtt` to the first chunk,
        `payload_land` from there."""
        got = 0
        trace.turn("request_rtt")
        try:
            for c in self.server._peer_stub(peer).VolumeEcShardRead(
                pb.EcShardReadRequest(
                    volume_id=self.vid, shard_id=shard_id, offset=offset,
                    size=size, generation=generation,
                ),
                timeout=30,
                # request id + trace context ride to the peer: a
                # degraded read's remote sibling fetches join the
                # reader's trace
                metadata=trace.grpc_metadata(),
            ):
                if got == 0:
                    trace.turn("payload_land")
                n = len(c.data)
                if got + n > size:
                    return False
                dst[got : got + n] = np.frombuffer(c.data, dtype=np.uint8)
                got += n
        except grpc.RpcError:
            return False
        return got == size

    def __call__(self, shard_id: int, offset: int, size: int, generation: int):
        """The range as `bytes` (None where no peer answered): the bare
        callable that `EcVolume` takes from a caller without buffers."""
        dst = np.empty(size, dtype=np.uint8)
        if self.read_into(shard_id, offset, size, generation, dst) is None:
            return None
        return dst.tobytes()


class VolumeServer:
    def __init__(
        self,
        directories: list[str],
        master: str = "localhost:9333",
        ip: str = "localhost",
        port: int = 8080,
        grpc_port: int = 0,
        max_volume_count: int = 8,
        ec_backend: str = "auto",
        data_center: str = "",
        rack: str = "",
        jwt_key: str = "",
        needle_map_kind: str = "memory",
        tls=None,
        ec_scrub_interval: float = 0.0,
        ec_scrub_bytes_per_sec: float = 64 << 20,
        ec_scrub_bad_retention: float = 0.0,
        ec_interval_cache_mb: int | None = None,
        ec_device_queue: bool = True,
        ec_queue_window: int | None = None,
        ec_queue_recovery_share: float | None = None,
        ec_queue_scrub_share: float | None = None,
        ec_placement: str = "auto",
        ec_trace: bool = False,
        ec_trace_ring: int = 0,
        ec_slow_op_s: float = 0.0,
        http_workers: int = 32,
        http_queue: int = 128,
    ):
        # Shared per-chip device-queue scheduler (ec/device_queue.py):
        # every EC producer on this server submits priority-tagged batch
        # streams (foreground encode/degraded reads > recovery rebuild/
        # decode > scrub) instead of owning a private device window.
        # `ec_device_queue=False` restores the PR 3 per-call-site
        # windows; the share knobs set each background class's minimum
        # fraction of admitted COST (output rows x bytes) under
        # contention. `ec_placement` picks the multi-chip stream routing
        # (ec/chip_pool.py): "auto" places whole streams on the
        # least-loaded chip (mesh only for a lone wide encode), "chip"
        # always places, "mesh" restores the PR 4 column-sliced shape.
        # The whole config lives in a PER-STORE QueueScope (threaded to
        # every producer below, like the interval cache) instead of the
        # old process-wide configure(): two servers embedded in one
        # process no longer clobber each other's scheduler knobs.
        shares = {}
        if ec_queue_recovery_share is not None:
            shares["recovery"] = ec_queue_recovery_share
        if ec_queue_scrub_share is not None:
            shares["scrub"] = ec_queue_scrub_share
        # Flight recorder (utils/trace.py): the tracer/ring/slow-op
        # threshold are process-wide (spans cross server objects in
        # embedded tests), so arming is strictly OPT-IN here — a second
        # server constructed with the defaults must not disarm the
        # first's recorder.
        if ec_trace or ec_trace_ring > 0 or ec_slow_op_s > 0:
            trace.configure(
                # slow-op logging needs spans recorded, so it arms too
                enabled=True if (ec_trace or ec_slow_op_s > 0) else None,
                ring_size=ec_trace_ring if ec_trace_ring > 0 else None,
                slow_op_s=ec_slow_op_s if ec_slow_op_s > 0 else None,
            )
        self.jwt_key = jwt_key
        self.ip = ip
        self.port = port
        self.grpc_port = grpc_port or (port + 10000)
        # `master` may be a comma-separated HA group; heartbeats follow
        # the raft leader via HeartbeatResponse.leader redirects
        self.master_addrs = [m.strip() for m in master.split(",") if m.strip()]
        self.master_addr = master
        self.master_grpc_addr = self._master_grpc(self.master_addrs[0])
        self.max_volume_count = max_volume_count
        self.data_center = data_center
        self.rack = rack
        self._mc = None
        self._mc_lock = threading.Lock()
        self._np_client = None
        self._peer_channels: dict[str, grpc.Channel] = {}
        # vid -> Lock: serializes peer-fetch rebuild per volume (the
        # staging dir is per-volume; concurrent runs would wipe each
        # other). dict.setdefault is atomic under the GIL.
        self._peer_rebuild_busy: dict[int, threading.Lock] = {}
        # Learned from HeartbeatResponse: the master's per-volume size
        # limit, the denominator for capacity-aware shard placement
        # (0 = not yet known -> slot-only planning).
        self.volume_size_limit = 0
        self.store = Store(
            directories,
            ip=ip,
            port=port,
            ec_backend=ec_backend,
            ec_remote_reader_factory=self._remote_reader_factory,
            needle_map_kind=needle_map_kind,
            # degraded-read reconstructed-interval cache budget shared
            # across ALL EC volumes on this server (one ChunkCache at
            # the Store); None keeps the store default, 0 disables
            ec_interval_cache_bytes=(
                None if ec_interval_cache_mb is None
                else int(ec_interval_cache_mb) << 20
            ),
            ec_device_queue=ec_device_queue,
            ec_queue_window=ec_queue_window,
            ec_queue_shares=shares,
            ec_placement=ec_placement,
        )
        self.service = VolumeService(self)

        # bulk-read fast path: a native Unix-socket sendfile server per
        # disk location (the RDMA sidecar analog, SURVEY §2.10); local
        # clients resolve ?locate=true then pull bytes kernel-to-kernel
        self.fastread_sockets: dict[str, str] = {}
        try:
            from ..utils.fastread import start_server as _fr_start

            for loc in self.store.locations:
                sock = os.path.join(loc.directory, ".fastread.sock")
                _fr_start(sock, loc.directory)
                self.fastread_sockets[
                    os.path.abspath(loc.directory)
                ] = sock
        except Exception as e:  # native toolchain absent: HTTP only
            logger("volume").warning("fastread sidecar disabled: %s", e)

        # Native shard byte plane (ec/net_plane.py): a TCP sidecar on
        # grpc_port + 10000 serving EC shard ranges with sendfile
        # egress — peers derive the address from the holder map's gRPC
        # address and fall back to the VolumeEcShardRead stream when
        # the port refuses. Runs even without the native .so (Python
        # egress), so the wire protocol is capability-stable.
        self.net_plane = None
        try:
            from ..ec import net_plane as _netp

            self.net_plane = _netp.ShardNetPlane(
                ip, _netp.derive_port(self.grpc_port),
                self._net_plane_resolve,
                server_label=f"{ip}:{port}",
                resolve_needle=self._net_plane_resolve_needle,
                resolve_write=self._net_plane_resolve_write,
                resolve_blob=self._net_plane_resolve_blob,
            )
        except Exception as e:  # port collision etc: gRPC-only peer
            logger("volume").warning("shard net plane disabled: %s", e)

        # named: the wait probes sum CPU by class of thread from the name
        self._grpc = grpc.server(
            futures.ThreadPoolExecutor(
                max_workers=32, thread_name_prefix="grpc-volume"
            )
        )
        rpc.add_service(self._grpc, rpc.VOLUME_SERVICE, self.service)
        self._grpc.add_insecure_port(f"{ip}:{self.grpc_port}")
        # Bounded worker-pool HTTP data plane (utils/http_pool.py):
        # `http_workers` request workers + an `http_queue`-deep
        # connection budget; saturation answers an explicit 503 +
        # Retry-After instead of spawning unbounded threads.
        # `http_workers=0` (or TLS) restores ThreadingHTTPServer.
        from ..utils.http_pool import build_http_server

        self._http = build_http_server(
            (ip, port),
            self._handler_class(),
            server_kind="volume",
            workers=http_workers,
            accept_queue=http_queue,
            tls=tls,
            reject_body=lambda: (
                "application/json",
                b'{"error": "volume server saturated: worker pool and '
                b'accept queue are full"}',
            ),
        )
        self.tls = tls
        if tls is not None:
            tls.wrap_server(self._http)
        self._http_thread = threading.Thread(
            target=self._http.serve_forever, daemon=True,
            name="http-accept-volume",
        )
        self._hb_queue: "queue.Queue[pb.Heartbeat]" = queue.Queue()
        self._hb_stop = threading.Event()
        self._hb_thread = threading.Thread(target=self._heartbeat_loop, daemon=True)

        # Background EC scrub/self-heal loop (ec/scrub.py). Off by
        # default (interval 0): enabling it is an operator decision —
        # with it off there is zero new background I/O or behavior.
        self.scrub_daemon = None
        if ec_scrub_interval > 0:
            from ..ec.scrub import ScrubDaemon

            self.scrub_daemon = ScrubDaemon(
                self.store,
                interval=ec_scrub_interval,
                bytes_per_sec=ec_scrub_bytes_per_sec,
                # 0 = keep quarantined .bad files forever (default)
                bad_retention_s=ec_scrub_bad_retention or None,
            )

    @staticmethod
    def _master_grpc(master: str) -> str:
        host, _, port = master.partition(":")
        return f"{host}:{int(port) + 10000}"

    def _net_plane_resolve(self, vid: int, sid: int, generation: int):
        """Shard fd + size for the native byte plane — the same checks
        (mounted, generation fence, shard local) as the gRPC servicer,
        refusals surfacing as protocol error messages."""
        from ..ec.net_plane import NetPlaneError

        ev = self.store.find_ec_volume(vid)
        if ev is None:
            raise NetPlaneError("ec volume not mounted")
        if generation and ev.encode_ts_ns != generation:
            raise NetPlaneError("stale generation")
        fd = ev.shard_fds.get(sid)
        if fd is None:
            raise NetPlaneError("shard not local")
        return fd, os.fstat(fd).st_size

    def _net_plane_resolve_needle(self, vid: int, nid: int, cookie: int):
        """Needle payload location for the net plane's chunk-read
        opcode (ISSUE 13) — the same control-plane checks as
        ``?locate=true`` (replicated volumes only; TTL'd/tiered/EC
        volumes refuse so those reads keep the locked, validated HTTP
        path). The fd is opened per request against the CURRENT .dat
        path — a vacuum commit mid-flight surfaces as the client's CRC
        mismatch, exactly like the fastread sidecar."""
        from ..ec.net_plane import NetPlaneError, NetPlaneVolumeRefusal

        vol = self.store.find_volume(vid)
        if vol is None:
            # EC or not mounted here: no needle on this volume will
            # ever serve — status 2 lets clients negative-cache the vid
            raise NetPlaneVolumeRefusal("volume not here (or EC)")
        try:
            path, off, size, crc = vol.locate_payload(nid, cookie)
        except VolumeError as e:
            # TTL'd/tiered/broken: volume-level, clients stop probing
            raise NetPlaneVolumeRefusal(str(e)) from None
        except Exception as e:
            # needle-level (not found, cookie mismatch): other needles
            # on the volume may still serve
            raise NetPlaneError(str(e)) from None
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError as e:
            raise NetPlaneError(str(e)) from None
        return fd, off, size, crc, True

    def _net_plane_resolve_write(
        self, vid: int, nid: int, cookie: int, data: bytes, md: dict
    ) -> tuple[int, int]:
        """Land one needle for the net plane's write opcode (ISSUE 18)
        — the exact Needle construction as the gRPC ``WriteNeedle``
        servicer so a plane write and a gRPC/HTTP write produce
        bit-identical records. JWT: keyed clusters require a
        volume-scoped token in ``x-sw-w-jwt`` (the same tokens peers
        sign for gRPC replication). Replica fan-out runs here unless
        the client marked the write ``x-sw-w-replicate: 0`` (it IS a
        replication leg)."""
        from ..ec.net_plane import (
            NetPlaneError,
            NetPlaneVolumeRefusal,
            _unb64,
        )

        if self.jwt_key:
            from ..utils.security import JwtError, verify_jwt

            try:
                # same scope rule as the HTTP gate: fid-scoped assign
                # tokens and volume-scoped peer tokens both pass
                verify_jwt(
                    self.jwt_key,
                    md.get("x-sw-w-jwt", ""),
                    str(FileId(vid, nid, cookie)),
                )
            except JwtError:
                raise NetPlaneError("unauthorized") from None
        try:
            flags = int(md.get("x-sw-w-flags", "0") or "0")
        except ValueError:
            flags = 0
        n = Needle(cookie=cookie, needle_id=nid, data=data, flags=flags)
        name = _unb64(md.get("x-sw-w-name", ""))
        if name:
            n.set_name(name)
        mime = _unb64(md.get("x-sw-w-mime", ""))
        if mime:
            n.set_mime(mime)
        fsync = True if md.get("x-sw-w-fsync") == "1" else None
        with M.request_seconds.time(server="volume", op="write"):
            try:
                size = self.store.write_needle(vid, n, fsync=fsync)
            except NotFoundError as e:
                # volume not mounted here: no needle will ever land —
                # status 2 lets clients negative-cache the vid
                raise NetPlaneVolumeRefusal(str(e)) from None
            except (ReadOnlyError, VolumeError, ValueError, OSError) as e:
                raise NetPlaneError(str(e)) from None
        M.request_total.inc(server="volume", op="write", code="ok")
        if md.get("x-sw-w-replicate") != "0":
            req = pb.WriteNeedleRequest(
                volume_id=vid,
                needle_id=nid,
                cookie=cookie,
                data=data,
                flags=flags,
                name=name.decode(errors="replace") if name else "",
                mime=mime.decode(errors="replace") if mime else "",
            )
            err = self.replicate_write(req)
            if err:
                raise NetPlaneError(f"replication: {err}")
        return size, n.checksum

    def _blob_root(self) -> str:
        root = os.environ.get("SEAWEED_EC_STREAM_BLOB_ROOT", "")
        if not root:
            root = os.path.join(
                self.store.locations[0].directory, "stream_shards"
            )
        return root

    def _net_plane_resolve_blob(self, path: str, op: str, md: dict):
        """Remote stream-shard blob landing for kind=blob writes — the
        transport behind ``net:`` durable-parity remote roots. Paths
        are confined to the blob root (env
        ``SEAWEED_EC_STREAM_BLOB_ROOT``, default
        ``<dir0>/stream_shards``); a path that escapes refuses. Returns
        an fd the plane pwrites+closes, or None when the op was handled
        here (unlink)."""
        from ..ec.net_plane import NetPlaneError

        if self.jwt_key:
            from ..utils.security import JwtError, verify_jwt

            try:
                verify_jwt(self.jwt_key, md.get("x-sw-w-jwt", ""), "blob")
            except JwtError:
                raise NetPlaneError("unauthorized") from None
        root = os.path.realpath(self._blob_root())
        full = os.path.realpath(os.path.join(root, path))
        if full != root and not full.startswith(root + os.sep):
            raise NetPlaneError("blob path escapes stream root")
        if op == "unlink":
            try:
                os.unlink(full)
            except FileNotFoundError:
                pass
            except OSError as e:
                raise NetPlaneError(str(e)) from None
            return None
        try:
            os.makedirs(os.path.dirname(full), exist_ok=True)
            return os.open(full, os.O_CREAT | os.O_RDWR, 0o644)
        except OSError as e:
            raise NetPlaneError(str(e)) from None

    # ----------------------------------------------------- remote shards

    def _master_client(self):
        """Lazy cached MasterClient (vid + EC lookup caches, persistent
        channel) — one per server, shared by all EC volumes."""
        with self._mc_lock:
            if self._mc is None:
                from ..client.master_client import MasterClient

                self._mc = MasterClient(self.master_addr)
            return self._mc

    def _net_plane_client(self):
        """Lazy shared NetPlaneClient for pull-side shard copies
        (VolumeEcShardsCopy / ec_migrate): pooled connections to peer
        sidecars, no-plane refusals memoized with TTL."""
        with self._mc_lock:
            if self._np_client is None:
                from ..ec.net_plane import NetPlaneClient

                self._np_client = NetPlaneClient()
            return self._np_client

    def _cluster_ec_telemetry(self) -> dict:
        """Heartbeat-learned per-node device telemetry from the
        master's /cluster/status (`EcTelemetry`: node_id -> chips/
        breakers/stage EWMAs) — the LIVE signal shard placement scores
        beside slots and disk headroom. Best-effort: any failure
        returns {} and planning degrades to the static scoring."""
        try:
            import requests as _requests

            mc = self._master_client()
            addr = getattr(mc, "_leader", "") or getattr(
                mc, "http_addr", ""
            )
            if not addr:
                return {}
            r = _requests.get(
                f"http://{addr}/cluster/status", timeout=2
            )
            r.raise_for_status()
            tele = r.json().get("EcTelemetry")
            return tele if isinstance(tele, dict) else {}
        except Exception:  # noqa: BLE001 — telemetry is advisory
            return {}

    def _peer_stub(self, peer: str):
        with self._mc_lock:
            ch = self._peer_channels.get(peer)
            if ch is None:
                ch = grpc.insecure_channel(peer)
                self._peer_channels[peer] = ch
            return rpc.volume_stub(ch)

    def _remote_reader_factory(self, vid: int, collection: str):
        return _PeerShardReader(self, vid)

    # ---------------------------------------------- peer-fetch rebuild

    def peer_fetch_rebuild(
        self, vid: int, collection: str = "", backend_name: str = ""
    ) -> dict:
        """Cluster-level EC self-heal for one volume on THIS server:
        when fewer than k verified-good source shards are on local
        disk, stream siblings from peer holders (VolumeEcShardRead,
        generation-fenced, sidecar-verified with verify-and-exclude —
        ec/peer_rebuild.py), rebuild through the staged/scheduled
        device path, mount the regenerated shards this server owns,
        and distribute regenerated CLUSTER-LOST shards to planned
        holders (ec/placement.py) before handing them off. Idempotent:
        a re-run after any crash window (publish, distribute)
        converges without minting duplicate copies."""
        # One peer rebuild per volume at a time on this server: a
        # concurrent second call (operator shell racing the fleet
        # dispatcher — the worker-control one-live-task dedupe only
        # covers tasks) would wipe the first call's staging directory
        # mid-flight. Refuse, don't queue: the first run heals the
        # volume and a refused caller re-runs idempotently.
        busy = self._peer_rebuild_busy.setdefault(vid, threading.Lock())
        if not busy.acquire(blocking=False):
            raise ECError(
                f"peer-fetch rebuild for ec volume {vid} is already "
                f"running on this server; re-run after it finishes"
            )
        try:
            return self._peer_fetch_rebuild_locked(
                vid, collection, backend_name
            )
        finally:
            busy.release()

    def _peer_fetch_rebuild_locked(
        self, vid: int, collection: str, backend_name: str
    ) -> dict:
        loc_base = self.service._ec_base(vid, collection)
        if loc_base is None:
            raise ECError(f"ec volume {vid} not found on this server")
        from ..ec.peer_rebuild import PeerFetchTransient, rebuild_from_peers
        from ..ec.volume_info import VolumeInfo

        vi = VolumeInfo.maybe_load(loc_base + ".vif")
        ctx = (vi.ec_ctx if vi else None) or ec_context.ECContext()
        generation = vi.encode_ts_ns if vi else 0
        ev = self.store.find_ec_volume(vid)
        if ev is None:
            # an unmounted volume has no legitimate-set to scope targets
            # by — distribution would ship this server's own shards
            # away. Offline repair keeps the local rebuild path.
            raise ECError(
                f"ec volume {vid} is not mounted here; peer-fetch "
                f"rebuild needs the serving mount"
            )
        legit = set(ev.legitimate_shards())

        # Fresh holder map (a balance move since the cached lookup would
        # route fetches at a server that no longer has the shard); the
        # master is REQUIRED here — without topology there is no safe
        # notion of "lost" vs "lives on a peer".
        try:
            located = self._master_client().lookup_ec(vid, refresh=True)
        except (LookupError, grpc.RpcError) as e:
            raise ECError(f"peer-fetch rebuild needs the master: {e}") from e
        me = f"{self.ip}:{self.port}"
        holders: dict[int, list[str]] = {}
        for sid, locs in located.items():
            peers = [fleet.grpc_addr(l) for l in locs if l.url != me]
            if peers:
                holders[sid] = peers
        lost = {sid for sid in range(ctx.total) if not located.get(sid)}
        present = {
            i
            for i in range(ctx.total)
            if os.path.exists(loc_base + ctx.to_ext(i))
        }
        # Same no-duplicate-minting contract as the local rebuild RPC:
        # regenerate only this server's legitimate set plus shards the
        # master knows no location for. Present-but-corrupt locals are
        # replaced by rebuild_from_peers regardless.
        targets = sorted((legit | lost) - present)

        def fetch(peer: str, sid: int, off: int, size: int) -> bytes:
            try:
                buf = bytearray()
                for c in self._peer_stub(peer).VolumeEcShardRead(
                    pb.EcShardReadRequest(
                        volume_id=vid,
                        shard_id=sid,
                        offset=off,
                        size=size,
                        generation=generation,
                    ),
                    timeout=60,
                    # one trace id across the whole cluster heal: the
                    # rebuild's span context rides to every peer's
                    # shard-read stream
                    metadata=trace.grpc_metadata(),
                ):
                    buf += c.data
                    M.net_bytes_copied_total.inc(len(c.data), plane="python", direction="read")
            except grpc.RpcError as e:
                # mid-stream peer death / stale generation / unreachable:
                # all retry-then-replan material, never a crash
                raise PeerFetchTransient(
                    f"{peer}: {e.code().name}: {e.details()}"
                ) from e
            M.net_bytes_received_total.inc(len(buf), plane="python", direction="read")
            M.net_bytes_copied_total.inc(len(buf), plane="python", direction="read")
            return bytes(buf)

        # Native ingress (ec/net_plane.py): sibling streams land
        # directly in pooled aligned buffers on the peer's shard byte
        # plane (grpc addr + port offset); peers without the plane are
        # memoized and their streams ride the gRPC fetch above.
        np_client = None
        fetch_into = None
        try:
            from ..ec import net_plane as _netp

            np_client = _netp.NetPlaneClient()
            fetch_into = _netp.make_fetch_into(np_client, vid, generation)
        except Exception:  # pragma: no cover - defensive
            np_client = None

        from ..ec.backend import get_backend

        backend = get_backend(
            backend_name or self.store.ec_backend,
            ctx.data_shards,
            ctx.parity_shards,
        )
        try:
            with M.request_seconds.time(server="volume", op="ec_peer_rebuild"):
                report = rebuild_from_peers(
                    loc_base,
                    holders,
                    fetch,
                    ctx=ctx,
                    targets=targets,
                    backend=backend,
                    scheduler=self.store.ec_scheduler,
                    fetch_into=fetch_into,
                )
        finally:
            if np_client is not None:
                np_client.close()
        M.ec_ops_total.inc(
            op="peer_rebuild", backend=backend_name or self.store.ec_backend
        )
        # Locally-owned regenerated shards re-enter service: swap the
        # mounted fds onto the fresh inodes (quarantined shards come
        # back too) and advertise via heartbeat. legit already covers
        # every corrupt shard this server may mount — served rot is in
        # shard_fds, quarantined rot rides legitimate_shards(); a
        # corrupt NON-legit file is a rotten handoff leftover, and
        # mounting it here would advertise a holder that the distribute
        # step below then unlinks.
        owned = sorted(sid for sid in report.rebuilt if sid in legit)
        if owned:
            ev.reopen_shards(owned)
            self.notify_new_ec_shards(vid, collection)
        # Leaf-repaired shards were patched IN PLACE on the canonical
        # inode: the serving fd stays valid, but cached reconstructions
        # over the patched byte ranges are stale — drop exactly those.
        for sid, ranges in report.patched_ranges.items():
            ev.invalidate_shard_ranges(sid, ranges)
        distributed = self._distribute_lost_shards(
            vid, collection, loc_base, ctx, legit
        )
        return {
            "rebuilt": sorted(report.rebuilt),
            "fetched": sorted(report.fetched),
            "distributed": distributed,
            "repaired": sorted(report.leaf_repaired),
        }

    def _distribute_lost_shards(
        self, vid: int, collection: str, base: str, ctx, legit
    ) -> list[int]:
        """Ship regenerated cluster-lost shards this server does NOT own
        to planned holders (copy + mount on the destination, then delete
        the local handoff copy). The inventory is the DISK — every
        canonical shard file outside this server's legitimate set — not
        just this run's rebuild output, so a re-run after a
        crash-during-distribute finishes the handoff instead of leaving
        limbo files; and the holder map is re-fetched HERE, so a crashed
        prior run whose destination already mounted the shard resolves
        by deleting the local duplicate instead of copying it to a
        second holder. The local copies are never mounted here, so the
        master never sees a duplicate holder mid-flight."""
        inventory = [
            sid
            for sid in range(ctx.total)
            if sid not in legit and os.path.exists(base + ctx.to_ext(sid))
        ]
        if not inventory:
            return []
        from .. import faults
        from ..ec.placement import node_view_for, plan_shard_placement

        try:
            located = self._master_client().lookup_ec(vid, refresh=True)
        except (LookupError, grpc.RpcError) as e:
            # the rebuild + local mounts above are already durable; a
            # re-run finishes the handoff. Typed refusal, not an
            # unhandled RpcError escaping the servicer as UNKNOWN.
            raise ECError(
                f"rebuilt shards are mounted, but distributing "
                f"cluster-lost shards needs the master: {e}; re-run "
                f"ec.rebuild -fromPeers to finish the handoff"
            ) from e
        me = f"{self.ip}:{self.port}"
        done: list[int] = []
        pending: list[int] = []
        for sid in inventory:
            if any(l.url != me for l in located.get(sid, [])):
                # a holder already serves it (crash-after-mount, or a
                # concurrent balance copy): finish the handoff — the
                # ec.balance dedupe rule — by dropping the local copy
                os.unlink(base + ctx.to_ext(sid))
                done.append(sid)
            else:
                pending.append(sid)
        if not pending:
            return done
        try:
            topo = self._master_client().topology()
        except (LookupError, grpc.RpcError) as e:
            raise ECError(
                f"rebuilt shards are mounted, but placing cluster-lost "
                f"shards needs the master topology: {e}; re-run "
                f"ec.rebuild -fromPeers to finish the handoff"
            ) from e
        nodes = {n.id: n for n in topo.nodes}
        # Live compute signal beside the capacity signal: the master's
        # heartbeat-learned per-node chip loads (EcTelemetry) rank
        # otherwise-equal destinations by queue headroom, so a
        # regenerated shard lands where there is compute slack for its
        # future degraded reads — the routing loop closed cluster-wide.
        cluster_tele = self._cluster_ec_telemetry()
        sp = trace.current()
        if sp is not None:
            sp.event(
                "placement_signals",
                source=("live" if cluster_tele else "static"),
                node_loads={
                    nid: t.get("chips", {})
                    and sum(
                        c.get("load", 0)
                        for c in t.get("chips", {}).values()
                    )
                    for nid, t in cluster_tele.items()
                },
            )
        # Capacity-aware views: used bytes straight from the topology
        # (volume sizes + EC shard bytes); the denominator is the
        # master's own volume size limit, learned via heartbeat. Either
        # side unknown -> headroom unknown -> slot-only planning.
        views = [
            node_view_for(
                n.id,
                n.rack,
                n.data_center,
                n.max_volume_count,
                len(n.volumes),
                n.ec_shards,
                ec_telemetry=cluster_tele.get(n.id),
                used_bytes=(
                    sum(int(v.size) for v in n.volumes)
                    + sum(
                        int(e.shard_size) * bin(e.shard_bits).count("1")
                        for e in n.ec_shards
                    )
                ),
                capacity_bytes=(
                    int(n.max_volume_count or 8) * self.volume_size_limit
                    if self.volume_size_limit > 0
                    else -1
                ),
            )
            for n in topo.nodes
        ]
        try:
            shard_bytes = os.path.getsize(base + ctx.to_ext(pending[0]))
        except OSError:
            shard_bytes = 0
        shard_count = {
            n.id: {e.id: bin(e.shard_bits).count("1") for e in n.ec_shards}
            for n in topo.nodes
        }
        faults.fire("ec.peer_rebuild.before_distribute", volume=vid)
        adopted: list[int] = []
        # In-pass re-planning: a destination that dies (or refuses) is
        # EXCLUDED and the remaining shards are re-planned against the
        # surviving candidates inside this same run — a dead holder no
        # longer defers the handoff to the next rebuild pass. Each
        # failed round excludes at least one node, so the loop is
        # bounded by the topology size.
        remaining = list(pending)
        dead_nodes: set[str] = set()
        for _round in range(max(len(views), 1) + 1):
            if not remaining:
                break
            candidates = [v for v in views if v.id not in dead_nodes]
            plan = plan_shard_placement(
                candidates, vid, remaining, shard_bytes=shard_bytes
            )
            if _round and plan:
                log.warning(
                    "re-planned ec %d distribution for shards %s after "
                    "excluding dead destinations %s",
                    vid, remaining, sorted(dead_nodes),
                )
            next_round: list[int] = []
            for sid in remaining:
                node = nodes.get(plan.get(sid, ""))
                if node is not None and node.id in dead_nodes:
                    # planned in THIS round before the node died on an
                    # earlier shard: don't burn another copy timeout on
                    # it — straight to the next round's re-plan
                    next_round.append(sid)
                    continue
                if node is None or node.location.url == me:
                    if _round:
                        # re-plan round after a destination death: no
                        # SURVIVING alternate can take it. Keep the
                        # handoff copy on disk (unmounted, never
                        # advertised) for the next rebuild run instead
                        # of adopting — a dead peer must not silently
                        # re-home the shard onto the rebuilder.
                        log.warning(
                            "ec %d.%02d: no surviving alternate "
                            "destination; handoff deferred to the next "
                            "run", vid, sid,
                        )
                        continue
                    # first plan: no capacity anywhere (or the planner
                    # chose us) — adopt the shard locally rather than
                    # leave it in limbo
                    adopted.append(sid)
                    done.append(sid)
                    continue
                dest = fleet.grpc_addr(node.location)
                first_on_dst = shard_count.get(node.id, {}).get(vid, 0) == 0
                try:
                    stub = self._peer_stub(dest)
                    stub.VolumeEcShardsCopy(
                        pb.EcShardsCopyRequest(
                            volume_id=vid,
                            collection=collection,
                            shard_ids=[sid],
                            source_url=f"{self.ip}:{self.grpc_port}",
                            copy_ecx=first_on_dst,
                            copy_ecj=first_on_dst,
                            copy_vif=first_on_dst,
                            copy_ecsum=first_on_dst,
                        ),
                        timeout=600,
                        metadata=trace.grpc_metadata(),
                    )
                    stub.VolumeEcShardsMount(
                        pb.EcShardsMountRequest(
                            volume_id=vid, collection=collection
                        ),
                        timeout=60,
                        metadata=trace.grpc_metadata(),
                    )
                except grpc.RpcError as e:
                    # destination died mid-distribute: exclude it and
                    # re-plan THIS shard against the survivors in the
                    # next round; the handoff copy stays on disk
                    # (unmounted, never advertised) either way, so a
                    # crash mid-re-plan still converges on re-run.
                    # Best-effort delete of whatever the COPY landed at
                    # the failed destination first: a copy-succeeded/
                    # mount-failed node keeps the shard at its canonical
                    # path, and once the shard is re-homed elsewhere a
                    # later mount on that node would advertise a
                    # duplicate holder. A dead node ignores the delete;
                    # a merely-slow one is cleaned.
                    log.warning(
                        "distribute ec %d.%02d -> %s failed: %s; "
                        "excluding the destination and re-planning",
                        vid, sid, dest, e.code().name,
                    )
                    try:
                        self._peer_stub(dest).VolumeEcShardsDelete(
                            pb.EcShardsDeleteRequest(
                                volume_id=vid,
                                collection=collection,
                                shard_ids=[sid],
                            ),
                            timeout=15,
                            metadata=trace.grpc_metadata(),
                        )
                    except grpc.RpcError:
                        pass  # node truly unreachable: nothing landed,
                        # or its disk state is beyond reach either way
                    dead_nodes.add(node.id)
                    next_round.append(sid)
                    continue
                faults.fire(
                    "ec.peer_rebuild.after_distribute", volume=vid, shard=sid
                )
                os.unlink(base + ctx.to_ext(sid))
                shard_count.setdefault(node.id, {})[vid] = (
                    shard_count.get(node.id, {}).get(vid, 0) + 1
                )
                done.append(sid)
            remaining = next_round
        if adopted:
            # mount ONLY the adopted ids: a blanket refresh would also
            # mount handoff copies whose distribute failed above, and
            # those must stay unmounted/unadvertised so the next run
            # retries the handoff instead of this server keeping them
            ev = self.store.find_ec_volume(vid)
            if ev is not None:
                ev.reopen_shards(adopted)
            self.notify_new_ec_shards(vid, collection)
        return done

    # ------------------------------------------------------- replication

    def _replica_locations(self, vid: int) -> list[pb.Location]:
        try:
            locs = self._master_client().lookup(vid)
        except (LookupError, grpc.RpcError):
            return []
        me = f"{self.ip}:{self.port}"
        return [l for l in locs if l.url != me]

    def _peer_metadata(self, vid: int):
        """Peer-auth metadata for gRPC writes on a keyed cluster."""
        if not self.jwt_key:
            return None
        from ..utils.security import sign_jwt

        return (("authorization", f"Bearer {sign_jwt(self.jwt_key, str(vid))}"),)

    def _plane_replicate(self, host: str, grpc_port: int,
                         request: pb.WriteNeedleRequest) -> bool:
        """One replication leg over the native write plane: a pooled
        sidecar connection instead of a per-write gRPC round trip.
        Returns False (caller falls back to gRPC) when the plane is
        off, chaos other than write-path chaos is armed, the peer has
        no sidecar (memoized with TTL), or the write errs — the gRPC
        leg is the correctness path, the plane leg only the fast one."""
        try:
            from ..ec import net_plane as _netp
            from ..ec import native_io

            if not native_io.enabled():
                return False
            if not _netp.write_plane_admissible():
                return False
            jwt = ""
            if self.jwt_key:
                from ..utils.security import sign_jwt

                jwt = sign_jwt(self.jwt_key, str(request.volume_id))
            self._net_plane_client().write_needle(
                (host, _netp.derive_port(grpc_port)),
                request.volume_id,
                request.needle_id,
                request.cookie,
                bytes(request.data),
                flags=request.flags,
                name=request.name.encode() if request.name else b"",
                mime=request.mime.encode() if request.mime else b"",
                jwt=jwt,
                replicate=False,
            )
            return True
        except Exception:  # noqa: BLE001 — any plane failure => gRPC
            return False

    def replicate_write(self, request: pb.WriteNeedleRequest) -> str:
        """Synchronous fan-out to replica holders (reference
        store_replicate.go:32 DistributedOperation). Each leg tries
        the native write plane first (pooled connection, fused-CRC
        landing), falling back to the per-write gRPC ``WriteNeedle``
        when the peer has no sidecar — both legs produce bit-identical
        needle records on the replica."""
        errors = []
        md = self._peer_metadata(request.volume_id)
        for loc in self._replica_locations(request.volume_id):
            host = loc.url.split(":")[0]
            if self._plane_replicate(host, loc.grpc_port, request):
                continue
            rep = pb.WriteNeedleRequest()
            rep.CopyFrom(request)
            rep.is_replicate = True
            try:
                r = self._peer_stub(
                    f"{host}:{loc.grpc_port}"
                ).WriteNeedle(rep, timeout=30, metadata=md)
                if r.error:
                    errors.append(f"{loc.url}: {r.error}")
            except grpc.RpcError as e:
                errors.append(f"{loc.url}: {e.code().name}")
        return "; ".join(errors)

    def replicate_ec_delete(self, vid: int, collection: str, needle_id: int) -> str:
        """Journal the EC tombstone on every other shard holder. Returns
        an error summary ('' = all holders reached) — a silently missed
        holder would resurrect the blob, so failures must surface."""
        try:
            # fresh holder list: a balance move since the cached lookup
            # would otherwise be missed entirely
            shard_locs = self._master_client().lookup_ec(vid, refresh=True)
        except (LookupError, grpc.RpcError) as e:
            return f"ec tombstone fan-out: holder lookup failed: {e}"
        me = f"{self.ip}:{self.port}"
        md = self._peer_metadata(vid)
        errors = []
        seen = set()
        for locs in shard_locs.values():
            for loc in locs:
                if loc.url == me or loc.url in seen:
                    continue
                seen.add(loc.url)
                try:
                    self._peer_stub(
                        f"{loc.url.split(':')[0]}:{loc.grpc_port}"
                    ).VolumeEcBlobDelete(
                        pb.EcBlobDeleteRequest(
                            volume_id=vid,
                            collection=collection,
                            needle_id=needle_id,
                        ),
                        timeout=30,
                        metadata=md,
                    )
                except grpc.RpcError as e:
                    errors.append(f"{loc.url}: {e.code().name}")
        return "; ".join(errors)

    def replicate_delete(self, request: pb.DeleteNeedleRequest) -> None:
        md = self._peer_metadata(request.volume_id)
        for loc in self._replica_locations(request.volume_id):
            rep = pb.DeleteNeedleRequest()
            rep.CopyFrom(request)
            rep.is_replicate = True
            try:
                self._peer_stub(
                    f"{loc.url.split(':')[0]}:{loc.grpc_port}"
                ).DeleteNeedle(rep, timeout=30, metadata=md)
            except grpc.RpcError:
                pass

    # -------------------------------------------------------- heartbeats

    def _ec_telemetry_json(self) -> str:
        """Device-telemetry blob riding every full heartbeat: per-chip
        queue load + breaker state (ec/chip_pool.chip_load_hint over
        this server's OWN scheduler scope), the flight recorder's
        EWMAs of the two device stages (what ec/placement.py sums, and
        no more), and per-EC-volume HEAT counters (lifetime
        read/reconstruction bytes — the master's rebalance scanner
        diffs them per sweep to rank hot volumes, ec/rebalance.py).
        The master is the only consumer — it aggregates into
        /cluster/status, the sw_ec_queue_load fleet gauges, and the
        gravity/heat planners; placement readers age the blob out via
        `received_at`/`ts` (SEAWEED_EC_TELEMETRY_STALE_S)."""
        from ..ec.chip_pool import chip_load_hint

        try:
            chips = chip_load_hint(self.store.ec_scheduler)
        except Exception:  # telemetry must never break the heartbeat
            chips = {}
        breakers_open = sum(
            1 for c in chips.values() if c.get("breaker") == "open"
        )
        ec_volumes: dict[str, dict] = {}
        try:
            for dloc in self.store.locations:
                for vid, ev in dloc.ec_volumes.items():
                    ec_volumes[str(vid)] = {
                        "read_bytes": int(ev.bytes_read),
                        "reconstructed_bytes": int(ev.bytes_reconstructed),
                    }
        except Exception:  # heat is advisory; never break the heartbeat
            ec_volumes = {}
        try:
            from ..ec.device_queue import residency_snapshot

            residency = residency_snapshot()
        except Exception:  # advisory; never break the heartbeat
            residency = {}
        return json.dumps(
            {
                "chips": chips,
                "breakers_open": breakers_open,
                "degraded": breakers_open > 0,
                "residency": residency,
                "stage_ewma_s": {
                    k: round(v, 6) for k, v in trace.stage_ewmas().items()
                },
                "ec_volumes": ec_volumes,
                "ts": time.time(),
            }
        )

    def _full_heartbeat(self) -> pb.Heartbeat:
        st = self.store.status()
        # addr label keeps multi-server processes from clobbering each
        # other on the shared registry
        addr = self.store.public_url
        M.volume_count.set(len(st["volumes"]), kind="normal", addr=addr)
        M.volume_count.set(len(st["ec_volumes"]), kind="ec", addr=addr)
        M.volume_bytes.set(
            sum(v["size"] for v in st["volumes"]), kind="normal", addr=addr
        )
        M.volume_bytes.set(
            sum(e["shard_size"] * len(e["shards"]) for e in st["ec_volumes"]),
            kind="ec",
            addr=addr,
        )
        return pb.Heartbeat(
            ip=self.ip,
            port=self.port,
            public_url=self.store.public_url,
            grpc_port=self.grpc_port,
            max_volume_count=self.max_volume_count,
            data_center=self.data_center,
            rack=self.rack,
            volumes=[
                pb.VolumeInfoMsg(
                    id=v["id"],
                    collection=v["collection"],
                    size=v["size"],
                    file_count=v["file_count"],
                    deleted_count=v["deleted_count"],
                    deleted_bytes=v["deleted_bytes"],
                    read_only=v["read_only"],
                    replica_placement=v["replica_placement"],
                    version=v["version"],
                    ttl=v.get("ttl", ""),
                    disk_type=v.get("disk_type", "hdd"),
                )
                for v in st["volumes"]
            ],
            ec_shards=[
                pb.EcShardInfoMsg(
                    id=e["id"],
                    collection=e["collection"],
                    shard_bits=_shard_bits(e["shards"]),
                    shard_size=e["shard_size"],
                    data_shards=e["data_shards"],
                    parity_shards=e["parity_shards"],
                    generation=e["generation"],
                )
                for e in st["ec_volumes"]
            ],
            has_no_volumes=not st["volumes"],
            has_no_ec_shards=not st["ec_volumes"],
            ec_telemetry_json=self._ec_telemetry_json(),
        )

    def notify_new_volume(self, vid: int) -> None:
        self._hb_queue.put(self._full_heartbeat())

    def notify_deleted_volume(self, vid: int) -> None:
        self._hb_queue.put(self._full_heartbeat())

    def notify_new_ec_shards(self, vid: int, collection: str) -> None:
        self._hb_queue.put(self._full_heartbeat())

    def notify_deleted_ec_shards(self, vid: int, collection: str, sids) -> None:
        self._hb_queue.put(self._full_heartbeat())

    def _heartbeat_iter(self):
        yield self._full_heartbeat()
        last_full = time.time()
        while not self._hb_stop.is_set():
            try:
                hb = self._hb_queue.get(timeout=2.0)
                yield hb
            except queue.Empty:
                # periodic full refresh doubles as liveness pulse; also
                # the reaper tick for expired TTL volumes
                reaped = self.store.reap_expired_volumes()
                if reaped:
                    log.info("reaped expired TTL volumes: %s", reaped)
                yield self._full_heartbeat()
                last_full = time.time()

    def _heartbeat_loop(self):
        target = self.master_addrs[0]
        fail_idx = 0
        while not self._hb_stop.is_set():
            redirect = None
            try:
                with grpc.insecure_channel(self._master_grpc(target)) as ch:
                    stream = rpc.master_stub(ch).SendHeartbeat(self._heartbeat_iter())
                    for resp in stream:
                        if self._hb_stop.is_set():
                            return
                        if resp.volume_size_limit:
                            self.volume_size_limit = int(
                                resp.volume_size_limit
                            )
                        if resp.leader and resp.leader != target:
                            # a follower answered: re-home to the leader
                            redirect = resp.leader
                            break
            except grpc.RpcError:
                pass
            if self._hb_stop.is_set():
                return
            if redirect:
                target = redirect
                continue  # reconnect immediately, no backoff
            # stream broke or follower with no known leader: try the
            # next configured master after a short pause
            fail_idx += 1
            target = self.master_addrs[fail_idx % len(self.master_addrs)]
            if self._hb_stop.wait(1.0):
                return

    # -------------------------------------------------------------- http

    def _handler_class(self):
        server = self

        from ..utils.request_id import RequestTracingMixin

        class Handler(RequestTracingMixin, BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"
            trace_server_kind = "volume"
            trace_addr = f"{server.ip}:{server.port}"

            def log_message(self, *a):
                pass

            def _error(self, code: int, msg: str) -> None:
                body = json.dumps({"error": msg}).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _fid(self):
                path = urlparse(self.path).path.lstrip("/")
                # accept "<vid>,<fid>" and "<vid>/<fid>"
                return FileId.parse(path.replace("/", ","))

            def _jwt_rejected(self, fid) -> bool:
                """True (and 401 already sent) when the cluster has a
                signing key and this request lacks a valid token
                (reference maybeCheckJwtAuthorization)."""
                if not server.jwt_key:
                    return False
                from ..utils.security import JwtError, verify_jwt

                auth = self.headers.get("Authorization", "")
                token = auth[7:] if auth.startswith("Bearer ") else ""
                try:
                    verify_jwt(server.jwt_key, token, str(fid))
                    return False
                except JwtError as e:
                    self._error(401, f"unauthorized: {e}")
                    return True

            def do_GET(self):
                u = urlparse(self.path)
                from ..utils.pprof import handle_debug_endpoint

                if handle_debug_endpoint(self, u):
                    return
                if self.serve_slo_endpoint(u.path):
                    return
                if u.path == "/debug/traces":
                    # Flight-recorder ring as Chrome trace_event JSON
                    # (load in Perfetto / chrome://tracing); ?trace_id=
                    # narrows to one cross-server trace, ?op= to one
                    # root op class, ?min_ms= to slow ops only;
                    # ?format=spans returns the raw span-tree docs
                    # instead. Loopback-only, same operator gate as
                    # /debug/pprof.
                    from ..utils.pprof import require_loopback

                    if not require_loopback(self, "trace"):
                        return
                    q = parse_qs(u.query)
                    tid = q.get("trace_id", [""])[0]
                    try:
                        min_ms = float(q.get("min_ms", ["0"])[0] or 0.0)
                    except ValueError:
                        min_ms = 0.0
                    docs = trace.traces(
                        tid, op=q.get("op", [""])[0], min_ms=min_ms
                    )
                    if q.get("format", [""])[0] == "spans":
                        payload = docs
                    else:
                        payload = trace.chrome_trace(docs=docs)
                    body = json.dumps(payload).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if u.path == "/metrics":
                    from ..utils.metrics import REGISTRY

                    body = REGISTRY.render()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if u.path == "/status":
                    st = server.store.status()
                    # per-chip per-class scheduler counters (depth /
                    # wait / throughput) ride along with volume status,
                    # keyed by each queue's `chip` device id — THIS
                    # server's scope, so a second tenant's chips never
                    # alias into these gauges. Pod breaker health rides
                    # on top: N of the M live chip queues with an OPEN
                    # fallback breaker (those chips' streams are running
                    # on CPU) flips `degraded`, the at-a-glance "this
                    # pod is not serving at device speed" flag.
                    snap = server.store.ec_scheduler.stats_snapshot()
                    open_b = sum(
                        1 for e in snap if e.get("breaker") == "open"
                    )
                    st["ec_device_queue"] = {
                        "queues": snap,
                        "chips": len(snap),
                        "breakers_open": open_b,
                        "degraded": open_b > 0,
                    }
                    if server.net_plane is not None:
                        # native shard byte plane sidecar health:
                        # sendfile vs python egress byte split
                        st["ec_net_plane"] = server.net_plane.status()
                    try:
                        from ..ec.stream_encode import stream_summary

                        # streaming-EC (encode-on-write) health: open
                        # streams in this process + parity-lag/sealed
                        # counters (sw_ec_stream_*)
                        st["ec_streams"] = stream_summary()
                    except Exception:  # noqa: BLE001
                        pass
                    try:
                        from ..ec.device_queue import residency_snapshot

                        # process-wide per-chip residency ledger:
                        # budget/inflight/high-watermarks + per-tenant
                        # shed counters (multi-tenant overload safety)
                        st["ec_residency"] = residency_snapshot()
                    except Exception:  # noqa: BLE001
                        pass
                    body = json.dumps(st).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                try:
                    # Per-tenant shedding on the needle data plane:
                    # reads against an over-share tenant's scope back
                    # off before any parse/lookup work when the
                    # residency ledger is at full shed (level 3) —
                    # the HTTP analogue of the S3 gateway's SlowDown,
                    # so direct volume readers see backpressure too.
                    from ..ec.device_queue import shed_advice

                    ra = shed_advice(
                        getattr(server.store.ec_scheduler, "tenant", "default")
                    )
                except Exception:  # shed is advisory; never block reads
                    ra = None
                if ra is not None:
                    body = json.dumps(
                        {"error": "tenant over fair device share"}
                    ).encode()
                    self.send_response(503)
                    self.send_header("Retry-After", str(max(1, int(ra))))
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                try:
                    fid = self._fid()
                except FileIdError as e:
                    return self._error(400, str(e))
                if parse_qs(u.query).get("locate", [""])[0] == "true":
                    # control plane of the bulk-read fast path: where
                    # the payload bytes live + which sidecar socket
                    # serves them (utils/fastread.py)
                    vol = server.store.find_volume(fid.volume_id)
                    if vol is None:
                        return self._error(404, "volume not here (or EC)")
                    try:
                        path, off, size, crc = vol.locate_payload(
                            fid.needle_id, fid.cookie
                        )
                    except (NotFoundError, CookieMismatch) as e:
                        return self._error(404, str(e))
                    except VolumeError as e:
                        return self._error(409, str(e))
                    sock = ""
                    apath = os.path.abspath(path)
                    for d, s in server.fastread_sockets.items():
                        if apath.startswith(d + os.sep):
                            sock = s
                            break
                    body = json.dumps(
                        {
                            "path": apath,
                            "offset": off,
                            "size": size,
                            "crc32c": crc,
                            "socket": sock,
                        }
                    ).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                self._sw_op = "read"
                try:
                    # gateway stage: needle read (an EC degraded read
                    # below this opens its own ec.degraded_read child
                    # span under the same HTTP root via the ambient
                    # span, down to the chip)
                    with trace.stage(trace.current(), "volume.read"):
                        n = server.store.read_needle(
                            fid.volume_id, fid.needle_id, fid.cookie
                        )
                except (NotFoundError, ECError) as e:
                    return self._error(404, str(e))
                except (CookieMismatch, NeedleError) as e:
                    # a record that fails its CRC or does not parse is an
                    # error RESPONSE too, never a dropped connection
                    return self._error(404, str(e))
                except (VolumeError, ValueError, OSError) as e:
                    # volume closed/converted mid-read: an error RESPONSE,
                    # never a dropped connection
                    return self._error(503, str(e))
                ctype = n.mime.decode() if n.mime else "application/octet-stream"
                data = n.data
                # on-the-fly thumbnailing (reference weed/images,
                # volume_server_handlers_read.go:362-421)
                rq = parse_qs(u.query)
                etag = f"{n.checksum:08x}"
                if "width" in rq or "height" in rq:
                    from ..utils.images import detect_format, resized

                    try:
                        rw = int(rq.get("width", ["0"])[0] or 0)
                        rh = int(rq.get("height", ["0"])[0] or 0)
                    except ValueError:
                        rw = rh = 0  # malformed dims: serve the original
                    rmode = rq.get("mode", [""])[0]
                    if rmode not in ("", "fit", "fill"):
                        # whitelist: the value is echoed into the ETag
                        # header, so arbitrary bytes would be header
                        # injection (response splitting)
                        rmode = ""
                    out, _, _ = resized(data, rw, rh, rmode)
                    if out is not data:
                        data = out
                        # re-encode may change the container (GIF→PNG)
                        # and each variant needs its own cache key
                        fmt = detect_format(data)
                        if fmt:
                            ctype = f"image/{fmt.lower()}"
                        etag = f"{n.checksum:08x}-{rw}x{rh}{rmode}"
                total = len(data)
                status = 200
                content_range = None
                rng = self.headers.get("Range", "")
                if rng.startswith("bytes=") and self.command != "HEAD":
                    try:
                        lo_s, _, hi_s = rng[6:].split(",")[0].partition("-")
                        lo = int(lo_s) if lo_s else max(total - int(hi_s), 0)
                        hi = int(hi_s) if hi_s and lo_s else total - 1
                        if lo > hi or lo >= total:  # incl. any range on empty body
                            self.send_response(416)
                            self.send_header("Content-Range", f"bytes */{total}")
                            self.send_header("Content-Length", "0")
                            self.end_headers()
                            return
                        hi = min(hi, total - 1)
                        data = data[lo : hi + 1]
                        status = 206
                        content_range = f"bytes {lo}-{hi}/{total}"
                    except ValueError:
                        pass  # malformed Range: serve the full body
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.send_header("Accept-Ranges", "bytes")
                if content_range:
                    self.send_header("Content-Range", content_range)
                self.send_header("ETag", f'"{etag}"')
                self.end_headers()
                if self.command != "HEAD":
                    # needle payloads leave through the native
                    # scatter-gather sender on the pooled front end
                    from ..utils.http_pool import send_body

                    send_body(self, data)

            do_HEAD = do_GET

            def do_POST(self):
                u = urlparse(self.path)
                q = parse_qs(u.query)
                try:
                    fid = self._fid()
                except FileIdError as e:
                    return self._error(400, str(e))
                if self._jwt_rejected(fid):
                    return
                length = int(self.headers.get("Content-Length", "0"))
                body = self.rfile.read(length)
                name, mime, data = _parse_upload(self.headers, body)
                req = pb.WriteNeedleRequest(
                    volume_id=fid.volume_id,
                    needle_id=fid.needle_id,
                    cookie=fid.cookie,
                    data=data,
                    name=name,
                    mime=mime,
                    is_replicate=q.get("type", [""])[0] == "replicate",
                )
                resp = server.service.WriteNeedle(req, None)
                if resp.error:
                    return self._error(500, resp.error)
                body = json.dumps({"name": name, "size": resp.size}).encode()
                self.send_response(201)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_DELETE(self):
                u = urlparse(self.path)
                q = parse_qs(u.query)
                try:
                    fid = self._fid()
                except FileIdError as e:
                    return self._error(400, str(e))
                if self._jwt_rejected(fid):
                    return
                resp = server.service.DeleteNeedle(
                    pb.DeleteNeedleRequest(
                        volume_id=fid.volume_id,
                        needle_id=fid.needle_id,
                        is_replicate=q.get("type", [""])[0] == "replicate",
                    ),
                    None,
                )
                if resp.error:
                    if resp.freed_bytes:
                        # freed locally but fan-out incomplete
                        code = 500
                    elif "not found" in resp.error:
                        code = 404
                    else:
                        # transient (volume mid-conversion, IO): 503 so
                        # clients retry instead of treating it as gone
                        code = 503
                    return self._error(code, resp.error)
                body = json.dumps({"size": resp.freed_bytes}).encode()
                self.send_response(202)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        return Handler

    # -------------------------------------------------------- lifecycle

    def start(self) -> None:
        self._grpc.start()
        self._http_thread.start()
        self._hb_thread.start()
        if self.net_plane is not None:
            self.net_plane.start()
        if self.scrub_daemon is not None:
            self.scrub_daemon.start()

    def stop(self) -> None:
        self._hb_stop.set()
        if self.net_plane is not None:
            self.net_plane.stop()
        if self.scrub_daemon is not None:
            self.scrub_daemon.stop()
        if self.fastread_sockets:
            from ..utils.fastread import stop_server as _fr_stop

            for sock in self.fastread_sockets.values():
                _fr_stop(sock)
        self._grpc.stop(grace=0.5)
        self._http.shutdown()
        self._http.server_close()
        with self._mc_lock:
            if self._mc is not None:
                self._mc.close()
            if self._np_client is not None:
                self._np_client.close()
            for ch in self._peer_channels.values():
                ch.close()
            self._peer_channels.clear()
        self.store.close()


def _parse_upload(headers, body: bytes) -> tuple[str, str, bytes]:
    """multipart/form-data or raw body -> (name, mime, data)."""
    ctype = headers.get("Content-Type", "")
    if ctype.startswith("multipart/form-data"):
        import email.parser
        import email.policy

        msg = email.parser.BytesParser(policy=email.policy.HTTP).parsebytes(
            b"Content-Type: " + ctype.encode() + b"\r\n\r\n" + body
        )
        for part in msg.iter_parts():
            data = part.get_payload(decode=True)
            if data is None:
                continue
            return (
                part.get_filename() or "",
                part.get_content_type(),
                data,
            )
        return "", "", b""
    return "", ctype if ctype != "application/octet-stream" else "", body
