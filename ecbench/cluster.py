"""The system under test, brought up in this process: a master and one
volume server on ephemeral ports, the shell, and the RPCs the benchmark
uses to put a volume back between operations. Copied from
chip_smoke.py's `Cluster` (later PRs may change that file, not this
yardstick)."""

from __future__ import annotations

import os
import socket
import threading
import time

SHELL_TIMEOUT_S = 60.0


class BenchError(RuntimeError):
    """The run cannot go on: reported, exit code non-zero, no result."""


def free_port() -> int:
    """Ephemeral port whose +10000 gRPC shadow is free too."""
    while True:
        with socket.socket() as s:
            s.bind(("localhost", 0))
            p = s.getsockname()[1]
        if p + 10000 > 65535:
            continue
        with socket.socket() as s:
            try:
                s.bind(("localhost", p + 10000))
            except OSError:
                continue
        return p


def filesystem_of(path: str) -> tuple[str, str]:
    """(mount point, filesystem type) that holds `path`."""
    path = os.path.realpath(path)
    best = ("", "unknown")
    with open("/proc/mounts") as f:
        for line in f:
            parts = line.split()
            if len(parts) < 3:
                continue
            mnt, fstype = parts[1], parts[2]
            inside = path == mnt or path.startswith(mnt.rstrip("/") + "/")
            if inside and len(mnt) > len(best[0]):
                best = (mnt, fstype)
    return best


MEMORY_FILESYSTEMS = frozenset({"tmpfs", "ramfs", "devtmpfs"})


class CompileMeter:
    """Counts XLA compilations and their seconds (jax.monitoring)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == self.COMPILE:
            with self._lock:
                self.compiles += 1
                self.seconds += seconds

    def snapshot(self) -> tuple[int, float]:
        with self._lock:
            return self.compiles, self.seconds


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    reports none, as the CPU does)."""
    import jax

    peaks = []
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


class Cluster:
    """In-process master + one volume server over `data_dir`, which may
    already hold volumes: the store loads what it finds."""

    def __init__(self, data_dir: str, config: dict, traced: bool,
                 max_volumes: int = 16):
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        from seaweedfs_tpu.shell.commands import ShellEnv
        from seaweedfs_tpu.utils import trace

        self.data_dir = data_dir
        mport = free_port()
        self.master = MasterServer(ip="localhost", port=mport)
        self.master.start()
        self.vs = VolumeServer(
            directories=[data_dir],
            master=f"localhost:{mport}",
            ip="localhost",
            port=free_port(),
            max_volume_count=max_volumes,
            ec_backend=config["ec_backend"],
            ec_interval_cache_mb=config.get("ec_interval_cache_mb"),
            ec_trace=traced,
        )
        self.vs.start()
        # the tracer is process-wide: an untraced run keeps it off, a
        # traced one keeps every root of the window
        if traced:
            trace.configure(enabled=True, ring_size=100_000, ring_spans=1_000_000)
        else:
            trace.configure(enabled=False)
        trace.reset()
        deadline = time.time() + 30
        while not self.master.topo.nodes:
            if time.time() > deadline:
                raise BenchError("volume server did not register with the master")
            time.sleep(0.02)
        self.volume_host = ("localhost", self.vs.port)
        self.env = ShellEnv(f"localhost:{mport}")
        self.env.lock_wait = SHELL_TIMEOUT_S
        import grpc

        from seaweedfs_tpu.pb import rpc

        self._channel = grpc.insecure_channel(f"localhost:{self.vs.grpc_port}")
        self.stub = rpc.volume_stub(self._channel)

    # ------------------------------------------------------------ shell

    def shell(self, line: str) -> str:
        from seaweedfs_tpu.shell.commands import run_command

        out = run_command(self.env, line)
        if "error" in out.lower() or "not found" in out.lower():
            raise BenchError(f"`{line}` -> {out}")
        return out

    def hold_admin_lease(self) -> None:
        """`lock`, as an operator's session does before a batch: the
        workers' commands then run side by side under it, each with the
        lease of its own volume."""
        self.shell("lock")

    # -------------------------------------------------------------- rpcs

    def wait_volume_listed(self, vid: int, listed: bool = True) -> None:
        deadline = time.time() + SHELL_TIMEOUT_S
        while True:
            try:
                here = bool(self.env.master.lookup(vid, refresh=True))
            except LookupError:
                here = False
            if here == listed:
                return
            if time.time() > deadline:
                raise BenchError(f"master never {'listed' if listed else 'dropped'} volume {vid}")
            time.sleep(0.002)

    def wait_shards_dropped(self, vid: int, shard_ids) -> None:
        deadline = time.time() + SHELL_TIMEOUT_S
        while True:
            try:
                located = self.env.master.lookup_ec(vid, refresh=True)
            except LookupError:
                located = {}
            if not any(located.get(sid) for sid in shard_ids):
                return
            if time.time() > deadline:
                raise BenchError(f"master still lists shards {list(shard_ids)} of {vid}")
            time.sleep(0.002)

    def unmount_shards(self, vid: int, shard_ids) -> None:
        from seaweedfs_tpu.pb import cluster_pb2 as pb

        self.stub.VolumeEcShardsUnmount(
            pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=list(shard_ids)),
            timeout=SHELL_TIMEOUT_S,
        )

    def delete_shards(self, vid: int, shard_ids) -> None:
        from seaweedfs_tpu.pb import cluster_pb2 as pb

        self.stub.VolumeEcShardsDelete(
            pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=list(shard_ids)),
            timeout=SHELL_TIMEOUT_S,
        )

    def mount_volume(self, vid: int) -> None:
        from seaweedfs_tpu.pb import cluster_pb2 as pb

        r = self.stub.VolumeMount(
            pb.VolumeCommandRequest(volume_id=vid), timeout=SHELL_TIMEOUT_S
        )
        if r.error:
            raise BenchError(f"VolumeMount {vid}: {r.error}")

    # ------------------------------------------------------------ checks

    def backend_faults(self, k: int, m: int) -> tuple[int, int]:
        """(1 unless the server encodes on a JaxBackend, batches that any
        live FallbackBackend gave to the CPU)."""
        from seaweedfs_tpu.ec.backend import _FALLBACKS, JaxBackend, get_backend

        be = get_backend(self.vs.store.ec_backend, k, m)
        not_device = 0 if isinstance(be, JaxBackend) else 1
        fallen = sum(int(fb.fallback_batches) for fb in list(_FALLBACKS))
        return not_device, fallen

    def stop(self) -> None:
        self._channel.close()
        self.env.close()
        self.vs.stop()
        self.master.stop()
