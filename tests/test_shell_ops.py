"""Round-5 shell command family: each command exercised through its
RPCs against live servers (not just argument parsing).

Reference: weed/shell/command_volume_*.go, command_mq_*.go,
command_fs_configure.go, command_cluster_ps.go.
"""

from __future__ import annotations

import json
import time

import grpc
import pytest

from conftest import allocate_port as free_port
from seaweedfs_tpu.pb import cluster_pb2 as pb
from seaweedfs_tpu.pb import rpc
from seaweedfs_tpu.server.master import MasterServer
from seaweedfs_tpu.server.volume_server import VolumeServer
from seaweedfs_tpu.shell.commands import COMMANDS, ShellEnv, run_command


def wait_for(cond, timeout=15.0, msg="condition"):
    """Poll `cond` until it holds. A master look-up RAISES LookupError
    until the volume's heartbeat has landed: that is "not yet" too."""
    deadline = time.time() + timeout
    while True:
        try:
            if cond():
                return
        except LookupError:
            pass
        if time.time() > deadline:
            raise TimeoutError(msg)
        time.sleep(0.05)


@pytest.fixture
def pair(tmp_path):
    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vols = []
    for i in range(2):
        vs = VolumeServer(
            directories=[str(tmp_path / f"v{i}")],
            master=f"localhost:{mport}",
            ip="localhost",
            port=free_port(),
            ec_backend="cpu",
        )
        vs.start()
        vols.append(vs)
    wait_for(lambda: len(master.topo.nodes) >= 2, msg="registration")
    env = ShellEnv(f"localhost:{mport}")
    yield master, vols, env
    env.close()
    for vs in vols:
        vs.stop()
    master.stop()


def _mk_volume(vs, vid, data=b"x"):
    with grpc.insecure_channel(f"localhost:{vs.grpc_port}") as ch:
        stub = rpc.volume_stub(ch)
        stub.AllocateVolume(
            pb.AllocateVolumeRequest(volume_id=vid, replication="000"),
            timeout=10,
        )
        stub.WriteNeedle(
            pb.WriteNeedleRequest(
                volume_id=vid, needle_id=1, cookie=3, data=data,
                is_replicate=True,
            ),
            timeout=10,
        )


def test_command_count_at_least_90():
    assert len(COMMANDS) >= 90, sorted(COMMANDS)


def test_volume_copy_unmount_mount_cycle(pair, tmp_path):
    master, (a, b), env = pair
    _mk_volume(a, 41, b"copy-me")
    wait_for(lambda: env.master.lookup(41, refresh=True), msg="master sees 41")
    out = run_command(
        env,
        f"volume.copy -volumeId 41 -target localhost:{b.grpc_port} "
        f"-source localhost:{a.grpc_port}",
    )
    assert "copied volume 41" in out, out
    assert b.store.find_volume(41).read_needle(1).data == b"copy-me"
    # unmount on b: files stay, volume unregistered
    out = run_command(
        env, f"volume.unmount -volumeId 41 -node localhost:{b.grpc_port}"
    )
    assert "unmounted" in out, out
    assert b.store.find_volume(41) is None
    # remount: files load back
    out = run_command(
        env, f"volume.mount -volumeId 41 -node localhost:{b.grpc_port}"
    )
    assert "mounted" in out, out
    assert b.store.find_volume(41).read_needle(1).data == b"copy-me"


def test_volume_configure_replication(pair):
    master, (a, _b), env = pair
    _mk_volume(a, 43)
    wait_for(lambda: env.master.lookup(43, refresh=True), msg="lookup 43")
    out = run_command(
        env, "volume.configure.replication -volumeId 43 -replication 001"
    )
    assert "replication -> 001" in out, out
    v = a.store.find_volume(43)
    assert str(v.super_block.replica_placement) == "001"
    # persisted: survives a reopen of the superblock from disk
    from seaweedfs_tpu.storage.super_block import SUPER_BLOCK_SIZE, SuperBlock

    with open(v.dat_path, "rb") as f:
        sb = SuperBlock.from_bytes(f.read(SUPER_BLOCK_SIZE))
    assert str(sb.replica_placement) == "001"


def test_volume_vacuum_toggle(pair):
    master, (a, _b), env = pair
    _mk_volume(a, 45, b"payload")
    with grpc.insecure_channel(f"localhost:{a.grpc_port}") as ch:
        rpc.volume_stub(ch).DeleteNeedle(
            pb.DeleteNeedleRequest(volume_id=45, needle_id=1, is_replicate=True),
            timeout=10,
        )
    a.store.find_volume(45).flush()
    a.notify_new_volume(45)
    wait_for(
        lambda: any(
            45 in n.volumes and n.volumes[45].deleted_bytes > 0
            for n in master.topo.nodes.values()
        ),
        msg="master sees garbage",
    )
    assert any(
        vid == 45 for vid, _, _ in master.topo.garbage_candidates(0.01)
    )
    out = run_command(env, "volume.vacuum.disable -volumeId 45")
    assert "disabled" in out, out
    assert not any(
        vid == 45 for vid, _, _ in master.topo.garbage_candidates(0.01)
    )
    out = run_command(env, "volume.vacuum.enable -volumeId 45")
    assert "enabled" in out, out
    assert any(
        vid == 45 for vid, _, _ in master.topo.garbage_candidates(0.01)
    )


def test_cluster_ps_and_worker_list(pair):
    master, _vols, env = pair
    out = run_command(env, "cluster.ps")
    assert "master" in out and out.count("volumeServer") == 2, out
    out = run_command(env, "worker.list")
    assert "no workers connected" in out


def test_maintenance_config_roundtrip(pair):
    master, _vols, env = pair
    out = run_command(
        env,
        "maintenance.config -set balance_spread=3 "
        "-set lifecycle_interval_seconds=60 -set lifecycle_filer=f:123 "
        "-set ec_balance_interval_seconds=45 "
        "-set ec_scrub_interval_seconds=3600 "
        "-set ec_rebalance_interval_seconds=120",
    )
    doc = json.loads(out)
    assert doc["balance_spread"] == 3.0
    assert doc["lifecycle_interval_seconds"] == 60.0
    assert doc["lifecycle_filer"] == "f:123"
    assert doc["ec_balance_interval_seconds"] == 45.0
    assert doc["ec_scrub_interval_seconds"] == 3600.0
    assert doc["ec_rebalance_interval_seconds"] == 120.0
    assert master.balance_spread == 3.0
    assert master.lifecycle_filer == "f:123"
    assert master.ec_balance_interval == 45.0
    # the carried ROADMAP knob: fleet scrub period is now runtime-
    # settable over the RPC, not constructor-only — and 0 turns the
    # scanner back off without touching the other knobs
    assert master.ec_scrub_interval == 3600.0
    # the PR 15 carried knob: gravity-rebalance cadence is runtime-
    # settable too (proto3-optional field, read-modify-write semantics)
    assert master.ec_rebalance_interval == 120.0
    out = run_command(env, "maintenance.config -set ec_scrub_interval_seconds=0")
    assert json.loads(out)["ec_scrub_interval_seconds"] == 0.0
    assert master.ec_scrub_interval == 0.0
    assert master.ec_balance_interval == 45.0  # partial update untouched
    assert master.ec_rebalance_interval == 120.0  # partial update untouched
    out = run_command(env, "maintenance.config -set ec_scrub_interval_seconds=-5")
    assert "error" in out
    out = run_command(
        env, "maintenance.config -set ec_rebalance_interval_seconds=-1"
    )
    assert "error" in out


# --------------------------------------------------------------- MQ ops


@pytest.fixture
def broker():
    from seaweedfs_tpu.mq.broker import MqBrokerServer

    srv = MqBrokerServer(ip="127.0.0.1", grpc_port=free_port(), kafka_port=0)
    srv.start()
    yield srv
    srv.stop()


def test_mq_truncate_and_delete(broker):
    from seaweedfs_tpu.mq.client import MqClient

    env = ShellEnv("localhost:9333")
    c = MqClient(f"127.0.0.1:{broker.grpc_port}")
    c.configure_topic("trunc", partitions=1)
    for i in range(10):
        c.publish("trunc", key=b"k", value=f"v{i}".encode())
    out = run_command(
        env,
        f"mq.topic.truncate -broker 127.0.0.1:{broker.grpc_port} "
        "-topic trunc -beforeOffset 7",
    )
    assert "truncated 1 partition" in out, out
    log = broker.broker.topic("default", "trunc").logs[0]
    assert log.earliest_offset == 7
    assert log.next_offset == 10
    out = run_command(
        env,
        f"mq.topic.delete -broker 127.0.0.1:{broker.grpc_port} -topic trunc",
    )
    assert "deleted topic" in out, out
    with pytest.raises(KeyError):
        broker.broker.topic("default", "trunc")


def test_mq_compact_archives_segments(tmp_path):
    """compact with a filer-backed broker: sealed raw segments become
    parquet files."""
    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.mq.broker import MqBrokerServer
    from seaweedfs_tpu.mq.client import MqClient
    from seaweedfs_tpu.server.filer_server import FilerServer

    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[str(tmp_path / "v")], master=f"localhost:{mport}",
        ip="localhost", port=free_port(), ec_backend="cpu",
    )
    vs.start()
    wait_for(lambda: master.topo.nodes, msg="vs registers")
    filer = Filer(MemoryStore(), master=f"localhost:{mport}")
    fsrv = FilerServer(filer, ip="localhost", port=free_port())
    fsrv.start()
    broker = MqBrokerServer(
        ip="127.0.0.1", grpc_port=free_port(), kafka_port=0,
        filer=f"localhost:{fsrv.port}", segment_records=8,
    )
    broker.start()
    try:
        c = MqClient(f"127.0.0.1:{broker.grpc_port}")
        c.configure_topic("arch", partitions=1)
        for i in range(40):  # 5 sealed segments of 8
            c.publish("arch", key=b"k", value=f"v{i}".encode())
        env = ShellEnv(f"localhost:{mport}")
        out = run_command(
            env,
            f"mq.topic.compact -broker 127.0.0.1:{broker.grpc_port} "
            "-topic arch",
        )
        assert "archived" in out, out
        n = int(out.split("archived ")[1].split(" ")[0])
        assert n >= 1
        # parquet files now exist in the topic directory
        from seaweedfs_tpu.client.filer_client import list_dir

        names = [
            e["FullPath"]
            for e in list_dir(f"localhost:{fsrv.port}", "/topics/default/arch/0000")
        ]
        assert any(p.endswith(".parquet") for p in names), names
        # records still readable end to end (parquet fallback load)
        got = list(c.subscribe("arch", partition=0, start_offset=0))
        assert len(got) == 40
    finally:
        broker.stop()
        fsrv.stop()
        filer.close()
        vs.stop()
        master.stop()


# ----------------------------------------------------- filer-side config


def test_fs_configure_rules_apply(tmp_path):
    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.server.filer_server import FilerServer

    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[str(tmp_path / "v")], master=f"localhost:{mport}",
        ip="localhost", port=free_port(), ec_backend="cpu",
    )
    vs.start()
    wait_for(lambda: master.topo.nodes, msg="vs registers")
    filer = Filer(MemoryStore(), master=f"localhost:{mport}")
    fport = free_port()
    # the shell derives filer gRPC as HTTP+10000 (the CLI convention)
    fsrv = FilerServer(filer, ip="localhost", port=fport, grpc_port=fport + 10000)
    fsrv.start()
    try:
        env = ShellEnv(f"localhost:{mport}", filer=f"localhost:{fsrv.port}")
        out = run_command(
            env,
            "fs.configure -locationPrefix /hot/ -collection fast "
            "-ttlSec 3600",
        )
        assert "configured /hot/" in out, out
        rule = filer.path_conf("/hot/a.txt")
        assert rule["collection"] == "fast"
        assert rule["ttl_sec"] == 3600
        assert filer.path_conf("/cold/b.txt") == {}
        # writes under the prefix pick the rule's ttl up
        e = filer.write_file("/hot/a.txt", b"abc")
        assert e.attr.ttl_sec == 3600
        e2 = filer.write_file("/cold/b.txt", b"abc")
        assert e2.attr.ttl_sec == 0
        # show + delete
        assert "/hot/" in run_command(env, "fs.configure -show")
        run_command(env, "fs.configure -locationPrefix /hot/ -delete")
        assert filer.path_conf("/hot/a.txt") == {}
    finally:
        fsrv.stop()
        filer.close()
        vs.stop()
        master.stop()


def test_mount_configure_applies_to_new_mounts(tmp_path):
    from seaweedfs_tpu.filer import Filer, MemoryStore
    from seaweedfs_tpu.mount.weed_mount import FilerMount
    from seaweedfs_tpu.server.filer_server import FilerServer

    mport = free_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[str(tmp_path / "v")], master=f"localhost:{mport}",
        ip="localhost", port=free_port(), ec_backend="cpu",
    )
    vs.start()
    wait_for(lambda: master.topo.nodes, msg="vs registers")
    filer = Filer(MemoryStore(), master=f"localhost:{mport}")
    fport = free_port()
    fsrv = FilerServer(filer, ip="localhost", port=fport, grpc_port=fport + 10000)
    fsrv.start()
    try:
        env = ShellEnv(f"localhost:{mport}", filer=f"localhost:{fsrv.port}")
        out = run_command(
            env, "mount.configure -attrTtl 0.25 -readonly true"
        )
        assert "applies to newly started mounts" in out
        fm = FilerMount(f"localhost:{fsrv.port}")
        assert fm.attr_ttl == 0.25
        assert fm.readonly is True

        class _FI:
            class contents:
                flags = 0x1  # O_WRONLY

        import errno as _errno

        assert fm.open("/x", _FI) == -_errno.EROFS
        assert fm.mkdir("/d", 0o755) == -_errno.EROFS
        run_command(env, "mount.configure -readonly false")
        fm2 = FilerMount(f"localhost:{fsrv.port}")
        assert fm2.readonly is False
    finally:
        fsrv.stop()
        filer.close()
        vs.stop()
        master.stop()


def test_volume_tier_move_command(pair):
    """tier.move resolves a target node and rides volume.move through
    the real RPC chain."""
    master, (a, b), env = pair
    _mk_volume(a, 47, b"tiered")
    wait_for(lambda: env.master.lookup(47, refresh=True), msg="lookup 47")
    out = run_command(
        env, "volume.tier.move -volumeId 47 -targetDiskType hdd"
    )
    assert "moved volume 47" in out, out
    assert a.store.find_volume(47) is None
    assert b.store.find_volume(47).read_needle(1).data == b"tiered"


def test_volume_scrub_and_ec_scrub_repair_smoke(pair, tmp_path):
    """weed shell volume.scrub / ec.scrub -repair smoke: clean scrub,
    injected bitrot detected, -repair rebuilds, second scrub clean."""
    import os

    from seaweedfs_tpu.storage.volume import Volume

    master, (a, _b), env = pair
    _mk_volume(a, 61, b"scrub-payload" * 500)
    wait_for(lambda: env.master.lookup(61, refresh=True), msg="lookup 61")
    out = run_command(env, "volume.scrub -volumeId 61")
    assert "all clean" in out, out

    out = run_command(env, "ec.encode -volumeId 61 -backend cpu -keepSource")
    assert "encoded" in out or "ec" in out, out

    def ec_visible():
        # lookup_ec raises (rather than returning empty) until the
        # heartbeat registers the shards — treat that as "not yet"
        try:
            return env.master.lookup_ec(61, refresh=True)
        except LookupError:
            return False

    wait_for(ec_visible, msg="ec shards visible")
    out = run_command(env, "ec.scrub -volumeId 61")
    assert "all clean" in out, out

    # bit-flip one shard on disk, then scrub with -repair
    base = Volume.base_file_name(str(tmp_path / "v0"), "", 61)
    shard = base + ".ec03"
    assert os.path.exists(shard)
    with open(shard, "r+b") as f:
        f.seek(64)
        b = f.read(1)
        f.seek(64)
        f.write(bytes([b[0] ^ 0x10]))
    out = run_command(env, "ec.scrub -volumeId 61 -repair")
    assert "BITROT in shards [3]" in out, out
    assert "rebuilt shards [3]" in out, out
    out = run_command(env, "ec.scrub -volumeId 61")
    assert "all clean" in out, out

    # delete a shard file out from under the server: scrub flags the
    # advertised-but-missing file and -repair regenerates it
    os.unlink(base + ".ec07")
    out = run_command(env, "ec.scrub -volumeId 61 -repair")
    assert "MISSING" in out, out
    assert "rebuilt shards [7]" in out, out
    assert os.path.exists(base + ".ec07")
    out = run_command(env, "ec.scrub -volumeId 61")
    assert "all clean" in out and "MISSING" not in out, out


def test_truncate_read_clamps_to_earliest(broker):
    """Reads below the truncation point clamp UP to earliest instead of
    skipping the retained partial segment (review r5)."""
    from seaweedfs_tpu.mq.client import MqClient

    c = MqClient(f"127.0.0.1:{broker.grpc_port}")
    c.configure_topic("clamp", partitions=1)
    for i in range(10):
        c.publish("clamp", key=b"k", value=f"v{i}".encode())
    broker.broker.truncate_topic("default", "clamp", before_offset=6)
    got = list(c.subscribe("clamp", partition=0, start_offset=0))
    assert [r.offset for r in got] == list(range(6, 10))
