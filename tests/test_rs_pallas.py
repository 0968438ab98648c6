"""Pallas fused RS kernel, interpret mode (CPU). Bit-exactness only: a
time comes from the chip (the cells' `rs_device_s_per_gib`, PERF.md)."""

import numpy as np
import pytest

from seaweedfs_tpu.ops import gf256, rs_jax, rs_pallas
from seaweedfs_tpu.ops.gf256 import ReedSolomon


@pytest.fixture(scope="module")
def ref():
    return ReedSolomon(10, 4)


def test_pallas_encode_bit_exact(ref, rng):
    import jax.numpy as jnp

    coeffs = gf256.parity_rows(10, 4)
    bm = jnp.asarray(rs_jax.bit_matrix_bitmajor(coeffs), jnp.float32)
    data = rng.integers(0, 256, size=(10, 600)).astype(np.uint8)
    got = np.asarray(
        rs_pallas.apply_bitmajor_pallas(
            bm, jnp.asarray(data), k=10, m=4, tile_n=128, interpret=True
        )
    )
    want = ref.encode(data)
    assert np.array_equal(got, want)


# The geometries that share the kernel (the MQ's 4+2, 10+4, 16+4: the
# contraction 8k is 32, 80 and 128 of the MXU's 128), in the form a
# JaxBackend stages: int32 words of four bytes. Widths in bytes against
# a tile of 128 words: a tile filled; a tile and a word the width does
# not fill; under one tile.
@pytest.mark.parametrize("n", [512, 517, 100])
@pytest.mark.parametrize("rows", ["encode", "rebuild2"])
@pytest.mark.parametrize("k,m", [(4, 2), (10, 4), (16, 4)])
def test_pallas_words_bit_exact_across_geometries(rng, k, m, rows, n):
    from seaweedfs_tpu.ec.backend import JaxBackend, _decode_coeffs

    rs = ReedSolomon(k, m)
    if rows == "encode":
        coeffs = rs.parity
    else:  # data shard 1 and the last parity shard from the k left
        lost = (1, k + m - 1)
        src = tuple(i for i in range(k + m) if i not in lost)[:k]
        coeffs = _decode_coeffs(rs.matrix, k, lost, src)
    bm = np.asarray(rs_jax.bit_matrix_bitmajor(coeffs), np.float32)
    data = rng.integers(0, 256, size=(k, n)).astype(np.uint8)
    words, width = JaxBackend._words(data)
    assert words.dtype == np.int32 and width == n
    m_out = coeffs.shape[0]
    got = np.asarray(
        rs_pallas.apply_bitmajor_pallas(
            bm, words, k=k, m=m_out, tile_n=128, interpret=True
        )
    )
    assert got.dtype == np.int32 and got.shape == (m_out, -(-n // 4))
    assert np.array_equal(
        got.view(np.uint8)[:, :n], gf256.matrix_apply(coeffs, data)
    )


def test_rsjax_pallas_impl_roundtrip(ref, rng):
    codec = rs_jax.RSJax(10, 4, impl="pallas", interpret=True, tile_n=128)
    data = rng.integers(0, 256, size=(10, 512)).astype(np.uint8)
    parity = np.asarray(codec.encode(data))
    assert np.array_equal(parity, ref.encode(data))
    full = np.concatenate([data, parity])
    present = {i: full[i] for i in range(14) if i not in (0, 12)}
    out = codec.reconstruct(present)
    for i in (0, 12):
        assert np.array_equal(np.asarray(out[i]), full[i])
    for other in ("pallas_", "aligned", ""):  # two names, nothing else
        with pytest.raises(ValueError, match="unknown impl"):
            rs_jax.RSJax(10, 4, impl=other)


def test_pallas_pad_edge(ref, rng):
    """Sizes not divisible by the tile exercise the pad path."""
    import jax.numpy as jnp

    coeffs = gf256.parity_rows(4, 2)
    bm = jnp.asarray(rs_jax.bit_matrix_bitmajor(coeffs), jnp.float32)
    ref42 = ReedSolomon(4, 2)
    for n in (1, 255, 513):
        data = rng.integers(0, 256, size=(4, n)).astype(np.uint8)
        got = np.asarray(
            rs_pallas.apply_bitmajor_pallas(
                bm, jnp.asarray(data), k=4, m=2, tile_n=128, interpret=True
            )
        )
        assert np.array_equal(got, ref42.encode(data)), n
