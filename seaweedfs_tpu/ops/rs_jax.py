"""Reed-Solomon GF(2^8) encode/reconstruct as XLA matmuls (TPU MXU).

The reference's hot loop (weed/storage/erasure_coding/ec_encoder.go:427
encodeDataOneBatch) calls klauspost's SIMD GF(2^8) multiply-accumulate.
On TPU there is no byte-gather ALU path, but GF(256) multiplication by a
constant is a *linear map over GF(2)^8*. An (m x k) GF(256) coefficient
matrix therefore expands to an (8m x 8k) 0/1 matrix B, and

    parity_bits = (B @ data_bits) mod 2

is an ordinary integer matmul — exactly what the MXU does — followed by
a cheap `& 1`. Accumulation values are bounded by 8k <= 2048 so f32/i32
accumulators are exact, and the result is bit-identical to the CPU path.

Two layouts are provided:

- `_apply_bits` (used by RSJax.encode/reconstruct): straightforward XLA
  path (unpack -> (8k, n) bits -> matmul -> pack). XLA fuses the
  shifts/masks around the matmul; HBM traffic is ~8x the byte count
  (bits stored as int8).
- `_apply_bits_bitmajor` + `bit_matrix_bitmajor`: a bit-major
  permutation of B so that unpack/pack touch only contiguous row/column
  blocks — the layout the fused Pallas kernel builds on to keep HBM
  traffic at 1x.
"""

from __future__ import annotations

import collections
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from . import gf256

# The one name of the Reed-Solomon apply on the device, whichever
# implementation runs: the Pallas kernel carries it (a profiler trace
# lists `sw_rs_apply[.N]` among the device operations), and the jitted
# glue around it (pad, pack, slice; the XLA path whole) lies under a
# named scope of the same name.
KERNEL_NAME = "sw_rs_apply"

# Accumulator dtype: int32 matmuls hit the MXU int8 path on v5e+; f32 is
# the safe fallback everywhere (values <= 2048 are exact in f32).
_ACC_DTYPE = jnp.float32


def bit_matrix(coeffs: np.ndarray) -> np.ndarray:
    """(m x k) GF(256) coeffs -> (8m x 8k) GF(2) matrix (byte-major)."""
    return gf256.expand_bit_matrix(np.asarray(coeffs, dtype=np.uint8))


def bit_matrix_bitmajor(coeffs: np.ndarray) -> np.ndarray:
    """Bit-major permutation of `bit_matrix`.

    Rows ordered bit-major: row (i*m + r) is output-bit i of byte-row r.
    Cols ordered bit-major: col (j*k + c) is input-bit j of byte-col c.
    With this layout, input bit-plane j of all k shards is the contiguous
    column block [j*k, (j+1)*k) and output bit-plane i is the contiguous
    row block [i*m, (i+1)*m) — no strided access inside a kernel.
    """
    m, k = np.asarray(coeffs).shape
    b = bit_matrix(coeffs)
    return (
        b.reshape(m, 8, k, 8).transpose(1, 0, 3, 2).reshape(8 * m, 8 * k).copy()
    )


def _lanes(data) -> jax.Array:
    """A batch as the device applies take it: (k, n) uint8 bytes or, the
    form in which ec/backend.py sends it over the host link, (k, n/4)
    int32 words of four consecutive bytes each (rs_pallas.py, "Words
    in, words out"). The result of an apply has the form of its input.
    Anything but int32 is taken as bytes."""
    if getattr(data, "dtype", None) == jnp.int32:
        return jnp.asarray(data)
    return jnp.asarray(data, dtype=jnp.uint8)


@functools.partial(jax.jit, static_argnames=())
@jax.named_scope(KERNEL_NAME)
def _apply_bits(b: jax.Array, data: jax.Array) -> jax.Array:
    """b: (8m, 8k) f32; data: (k, n) uint8 -> (m, n) uint8, or int32
    words -> words (`_lanes`): the four bytes of a word go through the
    one matmul on an axis of their own, so the columns stay columns (a
    column-sharded batch needs no collective)."""
    words = data.dtype == jnp.int32
    if words:
        data = jnp.stack(
            [((data >> (8 * i)) & 0xFF).astype(jnp.uint8) for i in range(4)],
            axis=1,
        )  # (k, 4, n/4)
    k = data.shape[0]
    m = b.shape[0] // 8
    rest = data.shape[1:]
    bit = jnp.arange(8, dtype=jnp.uint8).reshape((1, 8) + (1,) * len(rest))
    bits = ((data[:, None] >> bit) & 1).reshape((8 * k,) + rest).astype(_ACC_DTYPE)
    acc = jnp.tensordot(b, bits, axes=1, preferred_element_type=_ACC_DTYPE)
    pbits = (acc.astype(jnp.int32) & 1).reshape((m, 8) + rest)
    out = (pbits << bit.astype(jnp.int32)).sum(axis=1, dtype=jnp.int32)
    if words:
        return functools.reduce(
            jnp.bitwise_or, [out[:, i] << (8 * i) for i in range(4)]
        )
    return out.astype(jnp.uint8)


@functools.partial(jax.jit, donate_argnums=())
@jax.named_scope(KERNEL_NAME)
def _apply_bits_bitmajor(b: jax.Array, data: jax.Array) -> jax.Array:
    """Same contract as _apply_bits but with bit-major b (see above)."""
    k = data.shape[0]
    m = b.shape[0] // 8
    d = data.astype(jnp.int32)
    acc = jnp.zeros((8 * m, data.shape[1]), dtype=_ACC_DTYPE)
    for j in range(8):
        plane = ((d >> j) & 1).astype(_ACC_DTYPE)
        acc = acc + jnp.matmul(
            b[:, j * k : (j + 1) * k], plane, preferred_element_type=_ACC_DTYPE
        )
    out = jnp.zeros((m, data.shape[1]), dtype=jnp.int32)
    acci = acc.astype(jnp.int32)
    for i in range(8):
        out = out | ((acci[i * m : (i + 1) * m] & 1) << i)
    return out.astype(jnp.uint8)


class RSJax:
    """Jitted RS codec. All GF matrix work happens host-side (numpy);
    the device only ever sees 0/1 matmuls.

    Mirrors the call surface the reference uses (Encode / Reconstruct /
    ReconstructData, weed/storage/erasure_coding + store_ec.go).
    """

    def __init__(
        self,
        data_shards: int,
        parity_shards: int,
        impl: str = "xla",
        interpret: bool = False,
        tile_n: int | None = None,
    ):
        """impl: "xla" (portable) or "pallas" (fused TPU kernel, 1x HBM
        traffic — see rs_pallas.py); `interpret=True` runs the pallas
        kernel off-TPU for tests."""
        if impl not in ("xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}")
        self.k = data_shards
        self.m = parity_shards
        self.n = data_shards + parity_shards
        self.impl = impl
        self.interpret = interpret
        self.tile_n = tile_n
        self._ref = gf256.ReedSolomon(data_shards, parity_shards)
        self.matrix = self._ref.matrix
        self._expand = bit_matrix_bitmajor if impl == "pallas" else bit_matrix
        # numpy, not a device array: the chips of a pool share one codec
        # (ec/chip_pool.py), and an array committed to one device would
        # pin every dispatch there. jit converts at call time; the
        # matrix is tiny (8m x 8k floats), so the per-call transfer is
        # noise.
        self._parity_bits = np.asarray(
            self._expand(self._ref.parity), dtype=_ACC_DTYPE
        )
        # Bounded: shard-loss patterns are diverse in a long-lived volume
        # server; each entry pins an (8m x 8k) bit-matrix.
        self._decode_bits_cache: "collections.OrderedDict[tuple, np.ndarray]" = (
            collections.OrderedDict()
        )
        self._decode_cache_limit = 64
        # Raw-coefficient apply cache (the rebuild/degraded-read path
        # precomputes its decode coefficients once per shard-loss set and
        # then applies them to every batch — the expansion must not be
        # paid per batch).
        self._coeff_bits_cache: "collections.OrderedDict[bytes, np.ndarray]" = (
            collections.OrderedDict()
        )
        # The device-queue scheduler multiplexes several streams'
        # pipeline threads into ONE RSJax; move_to_end/popitem sequences
        # on the OrderedDict caches are not atomic under concurrent
        # lookups with different coefficient sets.
        self._cache_lock = threading.Lock()

    # -- encode ------------------------------------------------------------

    def _apply(self, bits: np.ndarray, data: jax.Array, m_out: int) -> jax.Array:
        if self.impl == "pallas":
            from . import rs_pallas

            kwargs = {}
            if self.tile_n is not None:
                kwargs["tile_n"] = self.tile_n
            return rs_pallas.apply_bitmajor_pallas(
                bits,
                data,
                k=int(data.shape[0]),
                m=m_out,
                interpret=self.interpret,
                **kwargs,
            )
        return _apply_bits(bits, data)

    def encode(self, data) -> jax.Array:
        """(k, n) uint8 data shards -> (m, n) uint8 parity shards (or
        words -> words: `_lanes`)."""
        data = _lanes(data)
        if data.shape[0] != self.k:
            raise ValueError(f"expected {self.k} data rows, got {data.shape[0]}")
        return self._apply(self._parity_bits, data, self.m)

    # -- reconstruct -------------------------------------------------------

    def _rows_bits(self, out_rows: tuple[int, ...], src_rows: tuple[int, ...]) -> np.ndarray:
        """Bit-matrix mapping shards[src_rows] -> shards[out_rows]."""
        key = (out_rows, src_rows)
        with self._cache_lock:
            cached = self._decode_bits_cache.get(key)
            if cached is not None:
                self._decode_bits_cache.move_to_end(key)
                return cached
        sub = self.matrix[list(src_rows), :]
        inv = gf256.invert(sub)  # (k, k): src shards -> data shards
        want = gf256.matmul(self.matrix[list(out_rows), :], inv)
        bits = np.asarray(self._expand(want), dtype=_ACC_DTYPE)
        with self._cache_lock:
            self._decode_bits_cache[key] = bits
            if len(self._decode_bits_cache) > self._decode_cache_limit:
                self._decode_bits_cache.popitem(last=False)
        return bits

    def reconstruct(
        self,
        shards: dict[int, jax.Array],
        data_only: bool = False,
        want: list[int] | None = None,
    ):
        """Recover missing shards from any >=k present ones (device matmul).

        `want` restricts the output to specific shard ids (fewer matrix
        rows); default regenerates every missing shard."""
        present = tuple(sorted(shards))
        if len(present) < self.k:
            raise ValueError(f"need {self.k} shards, have {len(present)}")
        if want is not None:
            targets = want
        else:
            targets = range(self.k if data_only else self.n)
        missing = tuple(i for i in targets if i not in shards)
        if not missing:
            return {}
        src = present[: self.k]
        data = jnp.stack([jnp.asarray(shards[i], dtype=jnp.uint8) for i in src])
        bits = self._rows_bits(missing, src)
        out = self._apply(bits, data, len(missing))
        return {idx: out[i] for i, idx in enumerate(missing)}

    # -- general apply -----------------------------------------------------

    def coeff_bits(self, coeffs: np.ndarray) -> np.ndarray:
        """Expanded bit-matrix for an arbitrary (m_out x k) GF(256)
        coefficient matrix, cached by content (host numpy, converted at
        call time like _parity_bits)."""
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        key = coeffs.shape[0].to_bytes(4, "little") + coeffs.tobytes()
        with self._cache_lock:
            cached = self._coeff_bits_cache.get(key)
            if cached is not None:
                self._coeff_bits_cache.move_to_end(key)
                return cached
        bits = np.asarray(self._expand(coeffs), dtype=_ACC_DTYPE)
        with self._cache_lock:
            self._coeff_bits_cache[key] = bits
            if len(self._coeff_bits_cache) > self._decode_cache_limit:
                self._coeff_bits_cache.popitem(last=False)
        return bits

    def apply(self, coeffs: np.ndarray, data) -> jax.Array:
        """out[r] = sum_j coeffs[r,j] * data[j] over GF(256), dispatched
        on the device WITHOUT blocking (the staged-apply primitive: the
        caller decides when to force the result with np.asarray)."""
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        if coeffs.ndim != 2 or coeffs.shape[1] != len(data):
            raise ValueError(
                f"coeffs {coeffs.shape} do not match {len(data)} data rows"
            )
        bits = jnp.asarray(self.coeff_bits(coeffs))
        return self._apply(bits, _lanes(data), coeffs.shape[0])

    def verify(self, shards) -> bool:
        shards = jnp.asarray(shards, dtype=jnp.uint8)
        parity = self.encode(shards[: self.k])
        return bool(jnp.array_equal(parity, shards[self.k :]))
