"""Compiles for a described (not attached) TPU v5e — the one file that
holds them.

libtpu's compiler is installed here and compiles for a topology that is
described, so what Mosaic or XLA:TPU refuses at the main path's real
widths is refused here, at no chip time. Nothing runs: a compile that
passes is not a chip run.

Only one process may load libtpu, and it keeps it until exit, so the
topology is described inside a module-scoped fixture of THIS file
(never at import, never in conftest.py, not autouse), and every compile
happens in the test's own process.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from seaweedfs_tpu.ops import rs_jax, rs_pallas
from seaweedfs_tpu.ops.rs_jax import RSJax
from seaweedfs_tpu.parallel.mesh import BLOCK_AXIS, MeshRS

MIB = 1 << 20


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without a chip: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices), (BLOCK_AXIS,))


def _shapes(sharding, k, m_out, width):
    """(data, bit-major/byte-major matrix) shape structs."""
    data = jax.ShapeDtypeStruct((k, width), jnp.uint8, sharding=sharding)
    bits = jax.ShapeDtypeStruct(
        (8 * m_out, 8 * k), jnp.float32, sharding=sharding
    )
    return data, bits


# (k, rows out, width): what the main path dispatches.
SHAPES = [
    pytest.param(10, 4, 1 * MIB, id="10+4@1MiB"),  # 1 GiB volume rows
    pytest.param(10, 4, 16 * MIB, id="10+4@16MiB"),  # DEFAULT_BATCH
    pytest.param(10, 2, 1 * MIB, id="rebuild2@1MiB"),  # 2-shard rebuild
    pytest.param(10, 2, 16 * MIB, id="rebuild2@16MiB"),  # ... its batches
    pytest.param(4, 2, 256 << 10, id="4+2@256KiB"),  # stream parity
    pytest.param(10, 4, 64 << 10, id="10+4@64KiB"),  # one bitrot leaf
]


@pytest.mark.parametrize("k,m_out,width", SHAPES)
def test_pallas_compact_compiles(one_chip, k, m_out, width):
    data, bits = _shapes(one_chip, k, m_out, width)
    compiled = rs_pallas.apply_bitmajor_pallas.lower(
        bits, data, k=k, m=m_out
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# a degraded GET's one-row result beside them, and the geometries that
# share the kernel (contraction 8k = 32 / 80 / 128 of the MXU's 128;
# ROADMAP Reach B10): (k, rows out, width)
WORD_SHAPES = SHAPES + [
    pytest.param(10, 1, 448 << 10, id="get1@448KiB"),
    pytest.param(16, 4, 1 * MIB, id="16+4@1MiB"),
    pytest.param(16, 4, 16 * MIB, id="16+4@16MiB"),
    pytest.param(16, 2, 16 * MIB, id="16+4-rebuild2@16MiB"),
    pytest.param(4, 2, 1 * MIB, id="4+2@1MiB"),
    pytest.param(4, 2, 16 * MIB, id="4+2@16MiB"),  # = its two-row rebuild
    pytest.param(4, 1, 16 * MIB, id="4+2-rebuild1@16MiB"),
]


@pytest.mark.parametrize("k,m_out,width", WORD_SHAPES)
def test_pallas_compact_compiles_on_words(one_chip, k, m_out, width):
    """The form in which `JaxBackend` stages every batch: int32 words
    of four bytes in, words out, and the result dense on the chip (a
    uint8 result is laid out four rows to a word, `(4,1)`)."""
    _, bits = _shapes(one_chip, k, m_out, width)
    words = jax.ShapeDtypeStruct((k, width // 4), jnp.int32, sharding=one_chip)
    compiled = rs_pallas.apply_bitmajor_pallas.lower(
        bits, words, k=k, m=m_out
    ).compile()
    root = next(
        ln for ln in compiled.as_text().splitlines()
        if "tpu_custom_call" in ln and "sw_rs_apply" in ln
    )
    assert f"s32[{m_out},{width // 4}]" in root and "(4,1)" not in root.split("custom-call")[0]


@pytest.mark.parametrize("k,m_out,width", SHAPES)
def test_xla_compiles(one_chip, k, m_out, width):
    data, bits = _shapes(one_chip, k, m_out, width)
    compiled = rs_jax._apply_bits.lower(bits, data).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_mesh_encode_and_apply_compile_over_four_chips(mesh4):
    """The combination `JaxBackend` builds by itself on a four-chip
    host: the Pallas kernel inside shard_map over the column mesh."""
    rs = RSJax(10, 4, impl="pallas")
    mrs = MeshRS(rs, mesh4)
    assert not mrs.pod_sharded
    cols = NamedSharding(mesh4, P(None, BLOCK_AXIS))
    # as `JaxBackend.to_device` puts a batch: int32 words, column-sharded
    data = jax.ShapeDtypeStruct((10, 4 * MIB), jnp.int32, sharding=cols)
    enc = mrs._encode.lower(data).compile()
    assert "tpu_custom_call" in enc.as_text()
    # every chip holds a quarter of the columns, nothing is gathered
    assert "all-gather" not in enc.as_text()

    bits = jax.ShapeDtypeStruct(
        (16, 80), jnp.float32, sharding=NamedSharding(mesh4, P())
    )
    app = mrs._apply_jit(2, 10).lower(bits, data).compile()
    assert "tpu_custom_call" in app.as_text()
