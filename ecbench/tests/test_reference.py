"""The plain reference shares nothing with the program; at a small size
on the CPU the two have to agree, and the control has to differ."""

import os

import numpy as np
import pytest

from ecbench import data
from ecbench import reference as R

LAYOUT = {
    "data_shards": 10, "parity_shards": 4,
    "large_block_bytes": 1 << 30, "small_block_bytes": 1 << 20,
    "bitrot_block_bytes": 16 << 20, "bitrot_leaf_bytes": 64 << 10,
}
PLAN = {
    "large_body_bytes": 1 << 20, "small_per_gib": 300,
    "small_min_bytes": 1024, "small_max_bytes": 65536, "layout_seed": 24,
}


@pytest.fixture(scope="module")
def encoded_volume(tmp_path_factory):
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as ctx
    from seaweedfs_tpu.ec.encoder import ec_encode_volume

    d = str(tmp_path_factory.mktemp("vol"))
    vol = data.fabricate_volume(d, 1, 2**31 + 3, 24 << 20, PLAN)
    ec_encode_volume(vol.base, ctx, CpuBackend(ctx))
    return vol


def test_coding_matrix_is_klauspost_s():
    from seaweedfs_tpu.ops import gf256

    assert np.array_equal(
        np.array(R.coding_matrix(10, 4)), np.asarray(gf256.ReedSolomon(10, 4).matrix)
    )
    assert np.array_equal(
        np.array(R.coding_matrix(4, 2)), np.asarray(gf256.ReedSolomon(4, 2).matrix)
    )
    assert R.gf_mul(R.gf_inv(0x53), 0x53) == 1


def test_reference_agrees_with_the_cpu_backend(encoded_volume):
    dat = np.memmap(encoded_volume.base + ".dat", dtype=np.uint8, mode="r")
    want = R.encode(dat, LAYOUT)
    assert R.compare_encoding(encoded_volume.base, want) == (0, 0)
    assert want.shard_size(0) == os.path.getsize(encoded_volume.base + ".ec00")


def test_the_control_is_told_apart(encoded_volume):
    dat = np.memmap(encoded_volume.base + ".dat", dtype=np.uint8, mode="r")
    broken = R.encode(dat, LAYOUT, drop_term=(2, 7))
    differing, fields = R.compare_encoding(encoded_volume.base, broken)
    assert differing == 1  # parity shard 12, and no other
    assert fields >= 1  # its CRCs


def test_both_kinds_of_rows_are_striped(tmp_path):
    from seaweedfs_tpu.ec.backend import CpuBackend
    from seaweedfs_tpu.ec.context import DEFAULT_EC_CONTEXT as ctx
    from seaweedfs_tpu.ec.encoder import write_ec_files

    vol = data.fabricate_volume(str(tmp_path), 1, 5, 24 << 20, PLAN)
    prot = write_ec_files(
        vol.base, ctx, CpuBackend(ctx), large_block_size=1 << 20,
        small_block_size=1 << 16,
    )
    prot.save(vol.base + ".ecsum")
    layout = dict(LAYOUT, large_block_bytes=1 << 20, small_block_bytes=1 << 16)
    dat = np.memmap(vol.base + ".dat", dtype=np.uint8, mode="r")
    assert R.compare_encoding(vol.base, R.encode(dat, layout)) == (0, 0)


def test_a_missing_or_short_output_differs(encoded_volume, tmp_path):
    dat = np.memmap(encoded_volume.base + ".dat", dtype=np.uint8, mode="r")
    want = R.encode(dat, LAYOUT)
    assert R.compare_encoding(str(tmp_path / "nothing"), want) == (14, 4)
