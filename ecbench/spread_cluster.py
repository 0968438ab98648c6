"""The system under test as a cluster: a master and SEVEN volume
servers brought up in this process, each with its own ports, data
directory, store, HTTP workers and interval cache, and the RPCs that
leave a volume's shards where upstream's `ec.encode` + `ec.balance`
leave them. `cluster.py`'s one-server `Cluster` is not edited; what it
exports (`BenchError`, `free_port`, the shell's time limit, and the two
methods that need nothing but the shell's environment) is used as it
stands.

The seven servers and the master are threads of ONE process on one
machine: one interpreter, one page cache, the loopback interface in
place of a network, one `JaxBackend` and one chip (the configuration's
`cuts.hosts` says so).
"""

from __future__ import annotations

import os
import time

from ecbench.cluster import SHELL_TIMEOUT_S, BenchError, Cluster, free_port

COPY_TIMEOUT_S = 300.0


class SpreadCluster:
    """In-process master + `servers` volume servers over
    `<data_dir>/vs<i>`; server 0's directory may already hold volumes."""

    def __init__(self, data_dir: str, config: dict, traced: bool, servers: int):
        import grpc

        from seaweedfs_tpu.pb import rpc
        from seaweedfs_tpu.server.master import MasterServer
        from seaweedfs_tpu.server.volume_server import VolumeServer
        from seaweedfs_tpu.shell.commands import ShellEnv
        from seaweedfs_tpu.utils import trace

        mport = free_port()
        self.master = MasterServer(ip="localhost", port=mport)
        self.master.start()
        self.servers: list = []
        self.dirs: list[str] = []
        for i in range(servers):
            d = os.path.join(data_dir, f"vs{i}")
            os.makedirs(d, exist_ok=True)
            vs = VolumeServer(
                directories=[d],
                master=f"localhost:{mport}",
                ip="localhost",
                port=free_port(),
                max_volume_count=16,
                ec_backend=config["ec_backend"],
                ec_interval_cache_mb=config.get("ec_interval_cache_mb"),
            )
            vs.start()
            self.servers.append(vs)
            self.dirs.append(d)
        # the tracer is process-wide and stays off through set-up, in a
        # traced run too: armed, the warm-up's 5,900 GETs take 55 s
        # longer, and a traced run then ends at the edge of the time a
        # run may take (`arm_tracer`)
        self.traced = traced
        trace.configure(enabled=False)
        trace.reset()
        deadline = time.time() + 30
        while len(self.master.topo.nodes) < servers:
            if time.time() > deadline:
                raise BenchError(
                    f"{len(self.master.topo.nodes)} of {servers} volume servers "
                    "registered with the master"
                )
            time.sleep(0.02)
        self.stopped: set[int] = set()
        self.env = ShellEnv(f"localhost:{mport}")
        self.env.lock_wait = SHELL_TIMEOUT_S
        self._channels = [
            grpc.insecure_channel(f"localhost:{vs.grpc_port}") for vs in self.servers
        ]
        self.stubs = [rpc.volume_stub(ch) for ch in self._channels]

    # `Cluster`'s own: both use `self.env` and nothing else
    shell = Cluster.shell
    wait_volume_listed = Cluster.wait_volume_listed

    # ----------------------------------------------------------- servers

    def live(self) -> list[int]:
        return [i for i in range(len(self.servers)) if i not in self.stopped]

    def host(self, i: int) -> tuple[str, int]:
        return ("localhost", self.servers[i].port)

    def grpc_addr(self, i: int) -> str:
        return f"localhost:{self.servers[i].grpc_port}"

    # ------------------------------------------------------------ spread

    def move_shards(self, vid: int, src: int, dst: int, shard_ids) -> None:
        """One move as the shell's `ec.balance` executes it: copy to the
        destination with the index, journal, volume info and sidecar
        (the destination's first shards of this volume), mount there,
        then unmount and delete at the source."""
        from seaweedfs_tpu.pb import cluster_pb2 as pb

        ids = [int(s) for s in shard_ids]
        self.stubs[dst].VolumeEcShardsCopy(
            pb.EcShardsCopyRequest(
                volume_id=vid, shard_ids=ids, source_url=self.grpc_addr(src),
                copy_ecx=True, copy_ecj=True, copy_vif=True, copy_ecsum=True,
            ),
            timeout=COPY_TIMEOUT_S,
        )
        self.stubs[dst].VolumeEcShardsMount(
            pb.EcShardsMountRequest(volume_id=vid), timeout=SHELL_TIMEOUT_S
        )
        self.stubs[src].VolumeEcShardsUnmount(
            pb.EcShardsUnmountRequest(volume_id=vid, shard_ids=ids),
            timeout=SHELL_TIMEOUT_S,
        )
        self.stubs[src].VolumeEcShardsDelete(
            pb.EcShardsDeleteRequest(volume_id=vid, shard_ids=ids),
            timeout=SHELL_TIMEOUT_S,
        )

    def located(self, vid: int) -> dict[int, set[str]]:
        """shard id -> gRPC addresses of its holders, as the master's
        `LookupEcVolume` says now."""
        try:
            found = self.env.master.lookup_ec(vid, refresh=True)
        except LookupError:
            return {}
        return {
            int(sid): {f"{loc.url.split(':')[0]}:{loc.grpc_port}" for loc in locs}
            for sid, locs in found.items() if locs
        }

    def wait_placement(self, vid: int, shards_of_server) -> None:
        """Until the master lists every shard at the server the
        placement gives it (a stopped server's at none), and nowhere
        else."""
        want: dict[int, set[str]] = {}
        for i, held in enumerate(shards_of_server):
            if i in self.stopped:
                continue
            for sid in held:
                want[int(sid)] = {self.grpc_addr(i)}
        deadline = time.time() + SHELL_TIMEOUT_S
        while True:
            have = self.located(vid)
            if have == want:
                return
            if time.time() > deadline:
                raise BenchError(
                    f"the master lists the shards of {vid} at {have}, the placement "
                    f"is {want}"
                )
            time.sleep(0.01)

    def arm_tracer(self) -> None:
        """The end of a traced run's set-up: from here the program
        records, and keeps every root of the window (a GET of this
        cluster leaves its own root and one for every peer's stream)."""
        from seaweedfs_tpu.utils import trace

        if self.traced:
            trace.configure(enabled=True, ring_size=100_000, ring_spans=1_000_000)

    def stop_server(self, i: int) -> None:
        """A dead host: the server's threads end and its ports close;
        its files stay in its directory."""
        self.servers[i].stop()
        self.stopped.add(i)

    # ------------------------------------------------------------ checks

    def backend_faults(self, k: int, m: int) -> tuple[int, int]:
        """(servers of the seven that do not encode on a JaxBackend,
        batches that any live FallbackBackend gave to the CPU)."""
        from seaweedfs_tpu.ec.backend import _FALLBACKS, JaxBackend, get_backend

        not_device = sum(
            0 if isinstance(get_backend(vs.store.ec_backend, k, m), JaxBackend) else 1
            for vs in self.servers
        )
        fallen = sum(int(fb.fallback_batches) for fb in list(_FALLBACKS))
        return not_device, fallen

    def shards_beyond_placement(self, vid: int, shards_of_server) -> int:
        """Shards that a live server has mounted beyond the ones the
        placement gives it: 0 where the spread is real."""
        extra = 0
        for i in self.live():
            ev = self.servers[i].store.find_ec_volume(vid)
            held = set(ev.shard_ids) if ev is not None else set()
            extra += len(held - {int(s) for s in shards_of_server[i]})
        return extra

    def stop(self) -> None:
        for ch in self._channels:
            ch.close()
        self.env.close()
        for i in self.live():
            self.servers[i].stop()
        self.master.stop()
