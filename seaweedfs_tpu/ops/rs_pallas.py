"""Fused Pallas TPU kernel for GF(2^8) Reed-Solomon bit-plane matmuls.

The XLA path in ops/rs_jax.py materialises the (8k, n) bit expansion in
HBM (~8x traffic). This kernel keeps the expansion in VMEM: each grid
step DMAs a byte tile, unpacks the 8 bit-planes, runs one MXU matmul
against the *bit-major* matrix (ops/rs_jax.bit_matrix_bitmajor layout:
plane j of the k shards is a contiguous block), packs the output bits
back to bytes, and writes the parity tile — HBM traffic stays ~1x in +
1x out.

The planes are 0/1 and a sum is at most 8k, exact in the int8 dot with
its int32 accumulator: bit-exact on the v5e (PRs 21 and 30).

Words in, words out. A (rows, n) uint8 array lives on the chip four
ROWS to a 32-bit word (`T(4,128)(4,1)`): a two-row result is half
holes, a one-row result three quarters, and the holes cross the host
link with the bytes; a dense one still pays a pass that picks the four
rows of each word apart (PERF.md section 6, PR 30: 90 ms for a
(2, 16 MiB) uint8 result, 169 for (1, 32 MiB), 49 for (4, 8 MiB), 11
for the same bytes as (2, 4 Mi) int32). So the kernel also takes the
batch as int32 words of four consecutive bytes (the host's free
`.view(np.int32)` of the same rows) and returns words: the kernel body
runs once per byte of the word (`_each_byte`), the MXU work per byte is
the same, and no lane is shuffled. ec/backend.py stages every batch
that way; a uint8 array handed in directly is computed as before.

Reference hot loop being replaced:
weed/storage/erasure_coding/ec_encoder.go:427 (encodeDataOneBatch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (memory spaces)

from .rs_jax import KERNEL_NAME

# Default column tile. Measured sweet spot on v5e for the int8
# single-dot kernel (8192 beat 16384 by ~25%); VMEM use is dominated by
# the (8k, TN) plane block + (8m, TN) accumulator.
TILE_N = 8192


def _each_byte(d_ref, out_ref, body) -> None:
    """`out_ref[:] = body(tile)`, where `body` maps an int32 tile whose
    lanes hold bytes to the same. Lanes that are int32 words of four
    bytes take one pass per byte, low to high: `body` reads bit j as
    `(x >> j) & 1`, so the bytes above the one it is handed do not
    reach its result."""
    d = d_ref[:].astype(jnp.int32)
    if d_ref.dtype != jnp.int32:
        out_ref[:] = body(d).astype(out_ref.dtype)
        return
    out = body(d)
    for byte in range(1, 4):
        out = out | (body(d >> (8 * byte)) << (8 * byte))
    out_ref[:] = out


def _rs_kernel(b_ref, d_ref, out_ref):
    """b_ref: (8m, 8k) bit-major; d_ref: (k, TN) uint8 bytes, or int32
    words of four bytes (`_each_byte`); out_ref: (m, TN) of the same.

    One contraction-(8k) dot per tile, not 8 contraction-k dots: the MXU
    is weight-stationary, so contraction length is utilization (80/128
    vs 10/128 for the default 10+4 codec — measured ~3x on v5e).

    All integer lane work is int32: arithmetic right-shift is safe
    because bit 0 sits below any sign-extension for shifts <= 7.
    """
    m = out_ref.shape[0]
    # 0/1 planes fit int8: the exact integer MXU path.
    b = b_ref[:].astype(jnp.int8)

    def body(d):
        planes = jnp.concatenate([(d >> j) & 1 for j in range(8)], axis=0)
        acci = jax.lax.dot_general(
            b,
            planes.astype(jnp.int8),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        out = jnp.zeros((m, d.shape[1]), dtype=jnp.int32)
        for i in range(8):
            out = out | ((acci[i * m : (i + 1) * m] & 1) << i)
        return out

    _each_byte(d_ref, out_ref, body)


@functools.partial(jax.jit, static_argnames=("k", "m", "tile_n", "interpret"))
@jax.named_scope(KERNEL_NAME)
def apply_bitmajor_pallas(
    b,
    data,
    *,
    k: int,
    m: int,
    tile_n: int = TILE_N,
    interpret: bool = False,
):
    """(8m x 8k) bit-major GF(2) matrix applied to (k, n) uint8 -> (m, n)
    uint8, or to the batch as int32 words of four bytes -> words.

    n is padded to a tile multiple internally (RS of zero bytes is zero,
    so padding never corrupts real columns).
    """
    n = data.shape[1]
    pad = (-n) % tile_n
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    n_padded = data.shape[1]
    n_bytes = n_padded * (4 if data.dtype == jnp.int32 else 1)
    out = pl.pallas_call(
        _rs_kernel,
        out_shape=jax.ShapeDtypeStruct((m, n_padded), data.dtype),
        grid=(n_padded // tile_n,),
        in_specs=[
            pl.BlockSpec((8 * m, 8 * k), lambda i: (0, 0)),
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m, tile_n), lambda i: (0, i)),
        interpret=interpret,
        name=KERNEL_NAME,
        cost_estimate=pl.CostEstimate(
            flops=2 * 8 * m * 8 * k * n_bytes,
            bytes_accessed=(k + m) * n_bytes + 64 * m * k * 4,
            transcendentals=0,
        ),
    )(b.astype(jnp.float32), data)
    return out[:, :n]
