"""Fused Pallas TPU kernel for GF(2^8) Reed-Solomon bit-plane matmuls.

The XLA path in ops/rs_jax.py materialises the (8k, n) bit expansion in
HBM (~8x traffic). This kernel keeps the expansion in VMEM: each grid
step DMAs a byte tile, unpacks the 8 bit-planes, runs 8 small MXU
matmuls against contiguous column blocks of the *bit-major* matrix
(ops/rs_jax.bit_matrix_bitmajor layout), packs the output bits back to
bytes, and writes the parity tile — HBM traffic stays ~1x in + 1x out.

Byte-packing trick (pack_width W in {1, 2, 4}): W consecutive bytes are
processed as one uint(8W) lane. Plane j of a word is `(w >> j) & MASK`
with MASK = 0x0101.. — each byte's bit j stays in its own byte lane.
Matmul sums are <= 8k <= 2048 per byte lane, so no carries cross byte
boundaries and the packed accumulator word holds each byte's exact sum.
Parity bits come back out with `(acc & MASK) << i`. Everything is
endian-agnostic because pack and unpack mirror each other.

Exactness — MEASURED ON REAL v5e HARDWARE, not just interpret mode:
the MXU executes "f32" matmuls as bf16 passes (8-bit mantissa) unless
precision=HIGHEST is requested. Packed pw=2 sums reach 80*0x0101=20560,
which bf16 silently rounds — the low byte of every output word corrupts
while interpret mode (true f32) passes. Consequences baked in here:

- pack_width=1 (sums <= 8k <= 128, exact even in bf16) is the DEFAULT,
  run as a single contraction-8k dot in int8 (exact integer MXU path,
  ~3x the f32 j-loop throughput on v5e);
- pack_width=2 f32 dots force precision=HIGHEST (exact, slower);
- pack_width=4 would need >24-bit exact accumulation — rejected.

Words in, words out. A (rows, n) uint8 array lives on the chip four
ROWS to a 32-bit word (`T(4,128)(4,1)`): a two-row result is half
holes, a one-row result three quarters, and the holes cross the host
link with the bytes; a dense one still pays a pass that picks the four
rows of each word apart (PERF.md section 6, PR 30: 90 ms for a
(2, 16 MiB) uint8 result, 169 for (1, 32 MiB), 49 for (4, 8 MiB), 11
for the same bytes as (2, 4 Mi) int32). So both kernels also take the
batch as int32 words of four consecutive bytes (the host's free
`.view(np.int32)` of the same rows) and return words: the kernel body
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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (memory spaces)

from . import gf256
from .rs_jax import KERNEL_NAME

# Default word-column tile. Measured sweet spot on v5e for the pw=1
# int8 single-dot kernel (8192 beat 16384 by ~25%); VMEM use is
# dominated by the (8k, TN) plane block + (8m, TN) accumulator.
TILE_N = 8192

_WORD_DTYPES = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}
_MASKS = {1: 0x01, 2: 0x0101, 4: 0x01010101}


def _each_byte(d_ref, out_ref, body) -> None:
    """`out_ref[:] = body(tile)`, where `body` maps an int32 tile whose
    lanes hold bytes (or uintW words of packed bytes) to the same.
    Lanes that are int32 words of four bytes take one pass per byte,
    low to high: `body` reads bit j as `(x >> j) & mask`, so the bytes
    above the one it is handed do not reach its result."""
    d = d_ref[:].astype(jnp.int32)
    if d_ref.dtype != jnp.int32:
        out_ref[:] = body(d).astype(out_ref.dtype)
        return
    out = body(d)
    for byte in range(1, 4):
        out = out | (body(d >> (8 * byte)) << (8 * byte))
    out_ref[:] = out


def _rs_kernel(k: int, m: int, pack_width: int, b_ref, d_ref, out_ref):
    """b_ref: (8m, 8k) bit-major; d_ref: (k, TN) uintW words, or int32
    words of four bytes (`_each_byte`).

    One contraction-(8k) dot per tile, not 8 contraction-k dots: the MXU
    is weight-stationary, so contraction length is utilization (80/128
    vs 10/128 for the default 10+4 codec — measured ~3x on v5e).

    All integer lane work is int32: Mosaic lacks uint32<->f32 casts,
    and arithmetic right-shift is safe because the masked bit positions
    (0, 8, 16, 24) sit below any sign-extension for shifts <= 7.
    """
    mask = _MASKS[pack_width]
    if pack_width == 4:
        raise NotImplementedError(
            "pack_width=4 needs >24-bit exact matmul accumulation, which "
            "the TPU MXU does not provide (int32 dots unsupported, f32 "
            "dots are inexact past 2^24)"
        )

    # 0/1 planes fit int8: exact integer MXU path, ~2x f32 rate. Packed
    # sums reach 8k * 0x0101 (~20k): exact only if the MXU really
    # accumulates f32 — HIGHEST forces the multi-pass f32 path (default
    # precision runs bf16 passes and corrupts the low byte of every
    # word; caught by the bit-exactness suite).
    b = b_ref[:].astype(jnp.int8 if pack_width == 1 else jnp.float32)

    def body(d):
        planes = jnp.concatenate([(d >> j) & mask for j in range(8)], axis=0)
        if pack_width == 1:
            acci = jax.lax.dot_general(
                b,
                planes.astype(jnp.int8),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        else:
            acci = jax.lax.dot_general(
                b,
                planes.astype(jnp.float32),
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            ).astype(jnp.int32)
        out = jnp.zeros((m, d.shape[1]), dtype=jnp.int32)
        for i in range(8):
            out = out | ((acci[i * m : (i + 1) * m] & mask) << i)
        return out

    _each_byte(d_ref, out_ref, body)


@jax.named_scope(KERNEL_NAME)
def _pallas_apply(
    kernel,
    b,
    data,
    *,
    k: int,
    out_rows: int,
    keep_rows: int,
    b_block: tuple,
    tile_n: int,
    pack_width: int,
    interpret: bool,
):
    """Shared pad → pack-to-words → pallas_call → unpack scaffolding.

    `out_rows` is the kernel's output block height (possibly padded);
    `keep_rows` is how many real parity rows the caller gets back.
    n is padded to a tile multiple internally (RS of zero bytes is zero,
    so padding never corrupts real columns). int32 `data` is the batch
    as words of four bytes and comes back as words; `pack_width` packs
    uint8 `data` only.
    """
    if pack_width not in _WORD_DTYPES:
        raise ValueError(f"pack_width must be 1, 2 or 4, got {pack_width}")
    words_in = data.dtype == jnp.int32
    if words_in:
        pack_width = 1  # a lane is a word already; the kernel walks its bytes
    n = data.shape[1]
    pad = (-n) % (tile_n * pack_width)
    if pad:
        data = jnp.pad(data, ((0, 0), (0, pad)))
    n_padded = data.shape[1]
    if pack_width > 1:
        words = jax.lax.bitcast_convert_type(
            data.reshape(k, n_padded // pack_width, pack_width),
            _WORD_DTYPES[pack_width],
        )
    else:
        words = data
    lane_dtype = jnp.int32 if words_in else _WORD_DTYPES[pack_width]
    n_bytes = n_padded * (4 if words_in else 1)
    grid = (words.shape[1] // tile_n,)
    zeros = (0,) * len(b_block)
    out_words = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((out_rows, words.shape[1]), lane_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(b_block, lambda i: zeros),
            pl.BlockSpec((k, tile_n), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((out_rows, tile_n), lambda i: (0, i)),
        interpret=interpret,
        name=KERNEL_NAME,
        cost_estimate=pl.CostEstimate(
            flops=2 * 8 * out_rows * 8 * k * n_bytes,
            bytes_accessed=(k + out_rows) * n_bytes + 64 * out_rows * k * 4,
            transcendentals=0,
        ),
    )(b.astype(jnp.float32), words)
    if pack_width > 1:
        out = jax.lax.bitcast_convert_type(out_words, jnp.uint8).reshape(
            out_rows, n_padded
        )
    else:
        out = out_words
    return out[:keep_rows, :n]


@functools.partial(
    jax.jit, static_argnames=("k", "m", "tile_n", "pack_width", "interpret")
)
def apply_bitmajor_pallas(
    b,
    data,
    *,
    k: int,
    m: int,
    tile_n: int = TILE_N,
    pack_width: int = 1,
    interpret: bool = False,
):
    """(8m x 8k) bit-major GF(2) matrix applied to (k, n) uint8 -> (m, n)."""
    return _pallas_apply(
        functools.partial(_rs_kernel, k, m, pack_width),
        b,
        data,
        k=k,
        out_rows=m,
        keep_rows=m,
        b_block=(8 * m, 8 * k),
        tile_n=tile_n,
        pack_width=pack_width,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Lane-aligned variant.
#
# The compact kernel above slices the (8m, 8k) bit-matrix on the LANE
# dimension at j*k offsets (k=10 for the default codec) and writes
# (m=4, TN) output blocks — both below Mosaic's minimum tile shapes
# ((8,128) f32 / (16,128) 16-bit / (32,128) 8-bit; see
# /opt/skills/guides/pallas_guide.md "Tiling Constraints"). Interpret
# mode accepts that; real-hardware Mosaic may not. This variant keeps
# every lane dimension a multiple of 128 and never slices lanes:
#
# - the matrix is pre-transposed host-side into 8 per-input-bit planes
#   bT[j] of shape (k, 8*m_pad), m_pad = ceil16(m), so the lane dim is
#   8*m_pad (a 128 multiple) and the j-planes are indexed on the leading
#   dim, not lane-sliced;
# - each plane matmul contracts the SUBLANE dim of both operands
#   (bT[j]: (k, 8*m_pad) x plane: (k, TN) -> (8*m_pad, TN)), so the odd
#   k=10 only ever appears as a contraction length;
# - the output block is (m_pad, TN) with m_pad padded to the out word
#   dtype's min sublane count (32/16/8 for 8/16/32-bit words); the
#   caller slices the m real rows off afterwards.
#
# Cost of alignment: the out write is m_pad/m wider than needed
# (16 vs 4 rows for 10+4) — ~1.2x of the input bytes instead of 0.4x.
# ---------------------------------------------------------------------------

# Word-column tile for the aligned kernel. VMEM is dominated by the
# (8*m_pad, TN) f32 accumulator: 128 * TN * 4B = 2 MiB at TN=4096.
TILE_N_ALIGNED = 4096


# Mosaic minimum sublane counts by word width (see the tiling table in
# the pallas guide): the output block height must not go below these.
_MIN_SUBLANES = {1: 32, 2: 16, 4: 8}


def _aligned_m_pad(m: int, pack_width: int) -> int:
    """Output rows padded to BOTH a 16 multiple (lane dim 8*m_pad must be
    a 128 multiple) and the min sublane count of the out word dtype."""
    gran = max(16, _MIN_SUBLANES[pack_width])
    return ((m + gran - 1) // gran) * gran


def bit_matrix_planes(coeffs: np.ndarray, pack_width: int = 1) -> np.ndarray:
    """(m x k) GF(256) coeffs -> (8, k, 8*m_pad) f32 plane stack.

    bT[j, c, i*m_pad + r] = bit (i) of gf_mul coefficient row r applied
    to input-bit j of byte-column c — i.e. expand_bit_matrix's entry
    [8r+i, 8c+j], padded so the lane dim is a multiple of 128 and the
    kernel's (m_pad, TN) output block is sublane-legal for the word
    dtype pack_width selects.
    """
    coeffs = np.asarray(coeffs, dtype=np.uint8)
    m, k = coeffs.shape
    m_pad = _aligned_m_pad(m, pack_width)
    b = gf256.expand_bit_matrix(coeffs).reshape(m, 8, k, 8)  # [r, i, c, j]
    out = np.zeros((8, k, 8, m_pad), dtype=np.float32)
    out[:, :, :, :m] = b.transpose(3, 2, 1, 0)  # [j, c, i, r]
    return out.reshape(8, k, 8 * m_pad)


def _rs_kernel_aligned(k: int, m_pad: int, pack_width: int, b_ref, d_ref, out_ref):
    """b_ref: (8, k, 8*m_pad); d_ref: (k, TN) uintW -> (m_pad, TN).

    Same single-contraction-(8k) + exactness rules as _rs_kernel (int8
    dot for pw=1, f32 HIGHEST for pw=2): the planes are stacked on the
    sublane axis and the j dimension of b collapses into the contraction.
    """
    mask = _MASKS[pack_width]
    if pack_width == 4:
        raise NotImplementedError(
            "pack_width=4 needs >24-bit exact matmul accumulation"
        )

    # rows j*k+c match plane order. Packed sums exceed 8 bits: the MXU's
    # default bf16 passes would corrupt them — HIGHEST forces the exact
    # multi-pass f32 path.
    b2 = b_ref[:].reshape(8 * k, 8 * m_pad).astype(
        jnp.int8 if pack_width == 1 else jnp.float32
    )

    def body(d):
        planes = jnp.concatenate([(d >> j) & mask for j in range(8)], axis=0)
        if pack_width == 1:
            acci = jax.lax.dot_general(
                b2,
                planes.astype(jnp.int8),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
        else:
            acci = jax.lax.dot_general(
                b2,
                planes.astype(jnp.float32),
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,
            ).astype(jnp.int32)
        out = jnp.zeros((m_pad, d.shape[1]), dtype=jnp.int32)
        for i in range(8):
            out = out | ((acci[i * m_pad : (i + 1) * m_pad] & mask) << i)
        return out

    _each_byte(d_ref, out_ref, body)


@functools.partial(
    jax.jit, static_argnames=("k", "m", "tile_n", "pack_width", "interpret")
)
def apply_planes_pallas(
    b_planes,
    data,
    *,
    k: int,
    m: int,
    tile_n: int = TILE_N_ALIGNED,
    pack_width: int = 1,
    interpret: bool = False,
):
    """Aligned-layout twin of apply_bitmajor_pallas.

    b_planes: (8, k, 8*m_pad) from bit_matrix_planes; data (k, n) uint8
    -> (m, n) uint8.
    """
    if pack_width not in _WORD_DTYPES:
        raise ValueError(f"pack_width must be 1, 2 or 4, got {pack_width}")
    m_pad = b_planes.shape[2] // 8
    if m_pad % _aligned_m_pad(1, pack_width):
        raise ValueError(
            f"b_planes m_pad={m_pad} is not sublane-legal for "
            f"pack_width={pack_width}; build it with "
            f"bit_matrix_planes(coeffs, pack_width={pack_width})"
        )
    if m > m_pad:
        raise ValueError(
            f"m={m} exceeds the {m_pad} rows b_planes encodes"
        )
    return _pallas_apply(
        functools.partial(_rs_kernel_aligned, k, m_pad, pack_width),
        b_planes,
        data,
        k=k,
        out_rows=m_pad,
        keep_rows=m,
        b_block=(8, k, 8 * m_pad),
        tile_n=tile_n,
        pack_width=pack_width,
        interpret=interpret,
    )

