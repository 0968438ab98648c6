"""Multi-device sharding tests on the 8-device virtual CPU platform.

conftest.py forces `--xla_force_host_platform_device_count=8` +
`jax_platforms=cpu` before any backend init, so every suite run exercises
the same Mesh/shard_map path the driver validates via
`__graft_entry__.dryrun_multichip`.

Semantics mirrored: the reference's encode hot loop
(weed/storage/erasure_coding/ec_encoder.go:427 encodeDataOneBatch) is
embarrassingly parallel over block columns; the distributed analog shards
the column dimension over devices (DP-over-blocks) with the bit-matrix
replicated, and only CRC-sized reductions cross the ICI.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from seaweedfs_tpu.ops import gf256
from seaweedfs_tpu.ops.rs_jax import RSJax, _apply_bits

K, M = 10, 4


@pytest.fixture(scope="module")
def mesh():
    devs = jax.devices()
    assert len(devs) >= 8, "conftest must provide >=8 virtual devices"
    return Mesh(np.array(devs[:8]), ("blocks",))


def test_virtual_platform_is_8_cpu_devices():
    devs = jax.devices()
    assert len(devs) >= 8
    assert all(d.platform == "cpu" for d in devs[:8])


def test_mesh_sharded_encode_bit_exact(mesh, rng):
    """Column-sharded encode over an 8-device mesh == CPU reference."""
    rs = RSJax(K, M)
    n = 8 * 512
    data = rng.integers(0, 256, size=(K, n), dtype=np.uint8)
    by_block = NamedSharding(mesh, P(None, "blocks"))
    ddata = jax.device_put(data, by_block)
    pbits = jax.device_put(rs._parity_bits, NamedSharding(mesh, P()))

    parity = jax.jit(
        _apply_bits, out_shardings=by_block
    )(pbits, ddata)
    np.testing.assert_array_equal(
        np.asarray(parity), gf256.ReedSolomon(K, M).encode(data)
    )
    # the output really is distributed: one shard per device
    assert len(parity.addressable_shards) == 8
    assert parity.addressable_shards[0].data.shape == (M, n // 8)


def test_mesh_reconstruct_two_lost_shards(mesh, rng):
    """Regenerate shards 3 and 11 on-device, sharded over blocks."""
    rs = RSJax(K, M)
    n = 8 * 256
    data = rng.integers(0, 256, size=(K, n), dtype=np.uint8)
    all_shards = np.concatenate([data, gf256.ReedSolomon(K, M).encode(data)])

    src_rows = tuple(i for i in range(K + M) if i not in (3, 11))[:K]
    rbits = rs._rows_bits((3, 11), src_rows)
    by_block = NamedSharding(mesh, P(None, "blocks"))
    src = jax.device_put(all_shards[list(src_rows)], by_block)

    rec = jax.jit(_apply_bits, out_shardings=by_block)(
        jax.device_put(rbits, NamedSharding(mesh, P())), src
    )
    np.testing.assert_array_equal(np.asarray(rec)[0], all_shards[3])
    np.testing.assert_array_equal(np.asarray(rec)[1], all_shards[11])


def test_shard_map_psum_checksum(mesh, rng):
    """Global verify reduction rides the mesh (psum), matching how the
    reference shares only per-shard CRCs between encoder workers."""
    rs = RSJax(K, M)
    n = 8 * 128
    data = rng.integers(0, 256, size=(K, n), dtype=np.uint8)
    by_block = NamedSharding(mesh, P(None, "blocks"))
    parity = jax.jit(_apply_bits, out_shardings=by_block)(
        jax.device_put(rs._parity_bits, NamedSharding(mesh, P())),
        jax.device_put(data, by_block),
    )

    def local_sum(x):
        return jax.lax.psum(jnp.sum(x.astype(jnp.uint32)), "blocks")

    checksum = shard_map(
        local_sum, mesh=mesh, in_specs=P(None, "blocks"), out_specs=P()
    )(parity)
    expected = gf256.ReedSolomon(K, M).encode(data).astype(np.uint64).sum()
    assert int(checksum) == int(expected % (1 << 32))


def test_dryrun_multichip_entrypoint():
    """The exact function the driver records in MULTICHIP_r{N}.json."""
    import importlib
    import sys
    import pathlib

    root = str(pathlib.Path(__file__).resolve().parent.parent)
    if root not in sys.path:
        sys.path.insert(0, root)
    mod = importlib.import_module("__graft_entry__")
    mod.dryrun_multichip(8)


def test_production_encoder_on_mesh_bit_exact(tmp_path):
    """The PRODUCTION encoder
    (ec_encode_volume via JaxBackend) shards batch columns across the
    virtual 8-device mesh and produces a bit-identical .ecsum to the
    single-device CPU backend (shared impl with dryrun_multichip)."""
    from seaweedfs_tpu.ec.selfcheck import mesh_encode_selfcheck

    mesh_encode_selfcheck(str(tmp_path), 8)


def test_mesh_backend_rejects_impossible_device_count():
    import pytest as _pytest

    from seaweedfs_tpu.ec.backend import JaxBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT

    with _pytest.raises(RuntimeError, match="need 64 devices"):
        JaxBackend(DEFAULT_EC_CONTEXT, impl="xla", n_devices=64)


def test_parallel_pkg_mesh_helpers(mesh, rng):
    """parallel/ helpers: sharded encode + psum checksum round trip."""
    import numpy as np

    from seaweedfs_tpu.ops import gf256
    from seaweedfs_tpu.ops.rs_jax import RSJax
    from seaweedfs_tpu.parallel import MeshRS, pad_cols

    rs = RSJax(10, 4, impl="xla")
    mrs = MeshRS(rs, mesh)
    data = rng.integers(0, 256, size=(10, 8 * 1024 + 3), dtype=np.uint8)
    padded, n = pad_cols(data, mrs.n_devices)
    handle = mrs.encode(mrs.put(padded))
    parity = np.asarray(handle)[:, :n]
    expected = gf256.ReedSolomon(10, 4).encode(data)
    np.testing.assert_array_equal(parity, expected)
    cks = mrs.global_checksum(handle)
    assert cks == int(expected.astype(np.uint64).sum() % (1 << 32))


def test_pallas_kernel_inside_the_column_mesh(rng):
    """What a multi-chip TPU host builds by itself: the Pallas kernel
    wrapped in shard_map (here in interpret mode), encode and a 2-row
    rebuild apply, through the backend's staged surface."""
    from seaweedfs_tpu.ec.backend import CpuBackend, JaxBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as ctx

    be = JaxBackend(ctx, impl="pallas", interpret=True, n_devices=4)
    assert be._mesh_rs.n_devices == 4 and not be._mesh_rs.pod_sharded
    cpu = CpuBackend(ctx)
    data = rng.integers(0, 256, size=(10, 4 * 8192 + 77), dtype=np.uint8)
    parity = be.to_host(be.encode_staged(be.to_device(data)))
    np.testing.assert_array_equal(parity, cpu.encode(data))
    coeffs = rng.integers(0, 256, size=(2, 10), dtype=np.uint8)
    out = be.to_host(be.apply_staged(coeffs, be.to_device(data)))
    np.testing.assert_array_equal(out, cpu.apply(coeffs, data))
