"""Pluggable Reed-Solomon compute backends: -ec.backend=cpu|tpu|auto.

The EC pipeline (encoder/rebuild/decoder/read-recovery) is written
against this interface; the reference's equivalent seam is the
reedsolomon.Encoder handed around weed/storage/erasure_coding.

- CpuBackend: C++ AVX2 PSHUFB GF(2^8) (native/seaweed_native.cpp), the
  klauspost-equivalent path. Default for latency-sensitive single-
  interval recovery (SURVEY.md hard part (d)).
- JaxBackend: bit-matrix matmul on the local JAX device (TPU MXU via
  XLA or the fused Pallas kernel). Best at bulk batches; bit-identical
  to the CPU path by construction.

All backends consume/produce numpy uint8 arrays of shape (rows, n).
"""

from __future__ import annotations

import functools
import itertools
import weakref
from typing import Protocol

import numpy as np

from .. import faults
from ..ops import gf256
from ..utils import metrics as _M
from ..utils import trace
from ..utils.glog import logger
from .context import ECContext, ECError

# XLA compilations as the program itself sees them: a request that meets
# a new shape pays seconds for one, and without a count of its own a
# server cannot say that it did, nor which request paid.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles_total = _M.REGISTRY.counter(
    "sw_ec_compiles_total", "XLA compilations since the first device backend"
)
_compile_seconds_total = _M.REGISTRY.counter(
    "sw_ec_compile_seconds_total", "seconds spent in XLA compilations"
)


def _on_jax_duration(event: str, seconds: float, **_kw) -> None:
    if event == _COMPILE_EVENT:
        _compiles_total.inc()
        _compile_seconds_total.inc(seconds)
        # jit compiles on the calling thread: its ambient span paid
        trace.event(trace.current(), "compile", seconds=round(seconds, 4))


@functools.lru_cache(maxsize=1)
def _watch_compiles() -> None:
    """Register the process's one compile listener (jax.monitoring keeps
    listeners for the life of the process)."""
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


class RSBackend(Protocol):
    ctx: ECContext

    def encode(self, data: np.ndarray) -> np.ndarray:
        """(k, n) data -> (m, n) parity."""
        ...

    # Async device pipeline hooks (overlap H2D / compute / D2H). On the
    # CPU backend these degenerate to identity + synchronous encode, so
    # the encoder pipeline is written once against this surface.
    def to_device(self, data: np.ndarray):
        """Stage host data toward the compute device (async when the
        backend is a device; returns a handle encode_staged accepts)."""
        ...

    def encode_staged(self, staged):
        """Dispatch encode on staged input; returns a result handle
        WITHOUT waiting for completion."""
        ...

    def apply_staged(self, coeffs: np.ndarray, staged):
        """Dispatch a general GF(256) apply (see `apply`) on staged
        input; returns a result handle WITHOUT waiting for completion.
        The staged analog of `apply` — what rebuild/decode/degraded
        reconstruction use to overlap H2D, compute, and D2H."""
        ...

    def to_host(self, result) -> np.ndarray:
        """Block until `result` is complete and return host uint8."""
        ...

    def reconstruct(
        self, shards: dict[int, np.ndarray], want: list[int] | None = None
    ) -> dict[int, np.ndarray]:
        """Any >=k present shards -> the missing shards (all of them, or
        just `want` — e.g. one shard on the latency-sensitive read path)."""
        ...

    def apply(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        """General GF(256) matrix apply: out[r] = sum_j coeffs[r,j]*data[j]."""
        ...


def _decode_coeffs(
    matrix: np.ndarray, k: int, out_rows: tuple[int, ...], src_rows: tuple[int, ...]
) -> np.ndarray:
    """Rows mapping shards[src_rows] (k of them) -> shards[out_rows]."""
    sub = matrix[list(src_rows), :]
    inv = gf256.invert(sub)
    return gf256.matmul(matrix[list(out_rows), :], inv)


class _BackendBase:
    def __init__(self, ctx: ECContext):
        self.ctx = ctx
        self._ref = gf256.ReedSolomon(ctx.data_shards, ctx.parity_shards)
        self.matrix = self._ref.matrix

    def _plan_reconstruct(
        self, shards: dict[int, np.ndarray], want: list[int] | None
    ) -> tuple[tuple[int, ...], tuple[int, ...]]:
        k, total = self.ctx.data_shards, self.ctx.total
        present = tuple(sorted(i for i in shards if 0 <= i < total))
        if len(present) < k:
            raise ECError(f"need {k} shards to reconstruct, have {len(present)}")
        targets = range(total) if want is None else want
        missing = tuple(i for i in targets if i not in shards)
        return present[:k], missing

    def reconstruct(
        self, shards: dict[int, np.ndarray], want: list[int] | None = None
    ) -> dict[int, np.ndarray]:
        src, missing = self._plan_reconstruct(shards, want)
        if not missing:
            return {}
        coeffs = _decode_coeffs(self.matrix, self.ctx.data_shards, missing, src)
        data = np.stack([np.asarray(shards[i], dtype=np.uint8) for i in src])
        out = self.apply(coeffs, data)
        return {idx: out[i] for i, idx in enumerate(missing)}

    def verify(self, shards: np.ndarray) -> bool:
        shards = np.asarray(shards, dtype=np.uint8)
        k = self.ctx.data_shards
        return bool(np.array_equal(self.encode(shards[:k]), shards[k:]))

    # Default (synchronous) pipeline hooks; device backends override.
    # apply_staged degenerates to the synchronous apply, so CpuBackend
    # output through the staged pipeline is bit-identical to apply() by
    # construction.
    def to_device(self, data: np.ndarray):
        return data

    def encode_staged(self, staged):
        return self.encode(staged)

    def apply_staged(self, coeffs: np.ndarray, staged):
        return self.apply(coeffs, staged)

    def to_host(self, result) -> np.ndarray:
        return np.asarray(result, dtype=np.uint8)


class CpuBackend(_BackendBase):
    """Native C++ SIMD GF(2^8); falls back to numpy tables if the .so
    is unavailable."""

    # Below this width, thread spawn overhead beats the win from
    # splitting columns; single-interval read recovery stays 1-thread.
    _MT_MIN_WIDTH = 1 << 20

    def __init__(self, ctx: ECContext):
        super().__init__(ctx)
        try:
            from ..utils import native

            self._apply_fn = native.rs_apply
            self._apply_mt = getattr(native, "rs_apply_mt", None)
        except Exception:
            self._apply_fn = gf256.matrix_apply
            self._apply_mt = None

    def apply(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        coeffs = np.asarray(coeffs, np.uint8)
        data = np.asarray(data, np.uint8)
        if (
            self._apply_mt is not None
            and data.ndim == 2
            and data.shape[1] >= self._MT_MIN_WIDTH
        ):
            return self._apply_mt(coeffs, data)
        return self._apply_fn(coeffs, data)

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self.apply(self._ref.parity, data)


class JaxBackend(_BackendBase):
    """Local JAX device(s) via bit-matrix matmuls.

    With more than one local device the PRODUCTION encode path shards
    batch columns across a 1-D mesh (parallel.MeshRS): parity is
    columnwise-independent, so the split is bit-exact and XLA inserts
    no collectives — each chip encodes its column slice (SURVEY §7
    stage 2: pjit across chips for large volumes). Single-device
    behavior is unchanged."""

    device = None  # where to_device puts: JAX's default; ChipBackend pins one

    def __init__(
        self,
        ctx: ECContext,
        impl: str = "auto",
        interpret: bool = False,
        n_devices: int | None = None,
    ):
        super().__init__(ctx)
        from ..ops.rs_jax import RSJax
        from ..utils.devices import local_devices

        _watch_compiles()
        info = local_devices()
        if impl == "auto":
            impl = "pallas" if info.platform == "tpu" else "xla"
        self._rs = RSJax(
            ctx.data_shards, ctx.parity_shards, impl=impl, interpret=interpret
        )
        want = info.count if n_devices is None else n_devices
        if want > info.count:
            # explicit request: fail loudly, never silently shrink
            raise RuntimeError(f"need {want} devices, have {info.count}")
        self._mesh_rs = None
        if want > 1:
            # shard_map wraps the impl's own per-chip encode (XLA or
            # Pallas) over the column mesh
            from ..parallel import MeshRS, make_mesh

            self._mesh_rs = MeshRS(self._rs, make_mesh(want))

    # -- the host link: a batch crosses it as 32-bit words, both ways.
    # A (rows, n) uint8 array lies on the chip four ROWS to a word, so a
    # two-row result is half holes and a one-row result three quarters,
    # the holes cross with the bytes, and even a dense one is picked
    # apart on the way (ops/rs_pallas.py "Words in, words out"; PERF.md
    # section 6, PR 30). The same bytes as int32 words of four
    # consecutive bytes are a free view on the host at either end, and
    # the kernels take and return them. A staged handle is
    # (device words, n): n is the batch's width in bytes, the words may
    # end in a pad.

    @staticmethod
    def _words(data: np.ndarray, n_devices: int = 1):
        """Host (k, n) uint8 -> ((k, n/4) int32 view of it, n). Only a
        width that does not fill its last word (or the devices' equal
        shares of words) is copied, into a zero pad: parity of a zero
        column is zero."""
        from ..parallel import pad_cols

        data = np.ascontiguousarray(data, dtype=np.uint8)
        data, n = pad_cols(data, 4 * n_devices)
        return data.view(np.int32), n

    @staticmethod
    def _fetch(words, n: int) -> np.ndarray:
        """Device words -> host (rows, n) uint8: the fetched buffer
        viewed as bytes, the pad cut by a slice; no second copy (the
        runtime hands a result of a few words back column-major: only
        that is copied)."""
        host = np.ascontiguousarray(np.asarray(words))
        trace.count("d2h_bytes", host.nbytes)
        trace.count("d2h_dense_bytes", host.nbytes)
        return host.view(np.uint8)[:, :n]

    def encode(self, data: np.ndarray) -> np.ndarray:
        words, n = self._words(data)
        return self._fetch(self._rs.encode(words), n)

    def apply(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        # put, call and fetch at once; armed, the parts of the caller's
        # `reconstruct` stage (a degraded read's one matrix)
        import jax

        trace.lap("put")
        words, n = self._words(data)
        trace.count("h2d_bytes", words.nbytes)
        trace.count("batches", 1)
        staged = jax.device_put(words)
        trace.lap("launch")
        out = self._rs.apply(coeffs, staged)
        if trace.armed:
            trace.lap("ready")
            out.block_until_ready()
            trace.lap("d2h")
        return self._fetch(out, n)

    # -- async pipeline: JAX dispatch is non-blocking, so staging batch
    # N+1 while batch N computes (and N-1 comes home) only requires NOT
    # forcing np.asarray between the stages. The encoder's bounded
    # queues provide the double-buffering window. The copy home is
    # asked for where the apply is launched (`copy_to_host_async`), so
    # it runs behind the sink's writes and the batch's wait in the
    # queue, and `to_host` finds the bytes there or waits for the rest.
    # The trace.lap / trace.count lines split the caller's h2d_dispatch
    # and device_drain stages where the bytes cross (utils/trace.py);
    # disarmed each is one module-bool check.
    def to_device(self, data: np.ndarray):
        import jax

        trace.lap("stage")
        mesh = self._mesh_rs
        words, n = self._words(data, 1 if mesh is None else mesh.n_devices)
        trace.count("h2d_bytes", words.nbytes)
        trace.count("batches", 1)
        trace.lap("put")
        if mesh is not None:
            return mesh.put(words), n
        return jax.device_put(words, self.device), n

    def encode_staged(self, staged):
        trace.lap("launch")
        words, n = staged
        rs = self._rs if self._mesh_rs is None else self._mesh_rs
        out = rs.encode(words)
        out.copy_to_host_async()
        return out, n

    def apply_staged(self, coeffs: np.ndarray, staged):
        trace.lap("launch")
        words, n = staged
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        if self._mesh_rs is None:
            out = self._rs.apply(coeffs, words)
        else:
            bits = self._rs.coeff_bits(coeffs)
            out = self._mesh_rs.apply(bits, words, coeffs.shape[0])
        out.copy_to_host_async()
        return out, n

    def to_host(self, result) -> np.ndarray:
        # TPU-side chaos hook: the kernel was LAUNCHED (encode_staged/
        # apply_staged dispatched it non-blocking, and asked for the
        # copy home) and this fetch is where a reset/hung device
        # actually surfaces. A raised IOError here models a mid-kernel
        # device reset, so FallbackBackend's to_host failover (CPU
        # replay of the carried host batch) is exercisable — not just
        # pre-dispatch death.
        faults.fire(
            "ec.device.kernel_fetch", impl=getattr(self._rs, "impl", "")
        )
        words, n = result
        if trace.armed:
            # the same fetch, with the wait for the result (upload,
            # kernel, the device's queue) told apart from what is left
            # of the copy home
            trace.lap("ready")
            words.block_until_ready()
            trace.lap("d2h")
        return self._fetch(words, n)


# Live FallbackBackend registry for the breaker-health gauge: sampled
# at /metrics scrape time (callback gauge), so an open breaker shows up
# without any code path having to remember to publish it. Weak refs —
# the gauge must never keep a dead backend (and its device state) alive.
_FALLBACKS: "weakref.WeakSet" = weakref.WeakSet()
_fallback_seq = itertools.count()


def _breaker_samples():
    # Dedupe by chip label: several live FallbackBackends can wrap the
    # SAME physical chip (one per pooled backend / Store / EC ratio),
    # and duplicate series in one exposition are invalid Prometheus —
    # the whole scrape would fail exactly when the pod is busy. Any
    # open breaker marks the chip degraded.
    by_chip: dict[str, float] = {}
    for be in list(_FALLBACKS):
        label = be.chip_label or f"{type(be.primary).__name__}@{be._seq}"
        is_open = 1.0 if be.breaker.state == "open" else 0.0
        by_chip[label] = max(by_chip.get(label, 0.0), is_open)
    for label, val in sorted(by_chip.items()):
        yield {"chip": label}, val


_M.REGISTRY.gauge(
    "sw_ec_chip_breaker_open",
    "EC device fallback breaker open per chip (1 = streams on CPU)",
    ("chip",),
    fn=_breaker_samples,
)


class FallbackBackend(_BackendBase):
    """Device backend with a verified CPU escape hatch, mid-batch.

    Wraps a primary (JaxBackend) and a CpuBackend producing bit-identical
    outputs by construction. Every staged handle carries the HOST copy of
    its batch alongside the device handle, so when the device dies
    between dispatch and drain (the to_host block is where a hung/reset
    TPU actually surfaces) the batch is re-encoded on CPU and the encode
    stream continues without data loss — the encoder pipeline never
    learns a failover happened.

    A circuit breaker (utils/retry.py) stops feeding a repeatedly-failing
    device: after `failure_threshold` consecutive device errors all
    batches go straight to CPU until the reset timeout admits a probe.
    InjectedCrash (a BaseException) is NOT absorbed — a simulated process
    death must not turn into a graceful failover.
    """

    def __init__(self, primary: RSBackend, fallback: "CpuBackend", breaker=None):
        self.ctx = primary.ctx
        self.primary = primary
        self.fallback = fallback
        # Both wrapped backends derive from the same ctx, so they share
        # one encoding matrix; expose it like every other backend does
        # (degraded reads precompute decode coefficients from it).
        self.matrix = fallback.matrix
        if breaker is None:
            from ..utils.retry import CircuitBreaker

            breaker = CircuitBreaker(failure_threshold=3, reset_timeout=60.0)
        self.breaker = breaker
        self.fallback_batches = 0  # observability: batches served by CPU
        # Chip identity when this wraps one chip of a pool
        # (ec/chip_pool.py): rides into the fault-point context so
        # chaos tests can kill ONE chip, and into queue stats labels.
        self.chip_label = getattr(primary, "chip_label", "")
        self._seq = next(_fallback_seq)
        _FALLBACKS.add(self)
        self._log = logger("ec.backend")

    # Deterministic caller errors (bad shape/dtype/shard-count): the CPU
    # would fail identically, so they re-raise untouched — counting them
    # against the breaker would demote a healthy device on user input.
    _CALLER_ERRORS = (TypeError, ValueError, ECError)

    def _device_failed(self, stage: str, e: Exception) -> None:
        if isinstance(e, self._CALLER_ERRORS):
            raise e
        self.breaker.record_failure()
        self._log.warning(
            "device backend failed in %s (%s); falling back to CPU "
            "(breaker %s)", stage, e, self.breaker.state,
        )

    # -- synchronous surface ------------------------------------------------

    def encode(self, data: np.ndarray) -> np.ndarray:
        if self.breaker.allows():
            try:
                faults.fire("ec.backend.device.encode", width=data.shape[1])
                out = self.primary.encode(data)
                self.breaker.record_success()
                return out
            except Exception as e:
                self._device_failed("encode", e)
        self.fallback_batches += 1
        return self.fallback.encode(data)

    def apply(self, coeffs: np.ndarray, data: np.ndarray) -> np.ndarray:
        if self.breaker.allows():
            try:
                faults.fire("ec.backend.device.apply")
                out = self.primary.apply(coeffs, data)
                self.breaker.record_success()
                return out
            except Exception as e:
                self._device_failed("apply", e)
        self.fallback_batches += 1
        return self.fallback.apply(coeffs, data)

    def reconstruct(
        self, shards: dict[int, np.ndarray], want: list[int] | None = None
    ) -> dict[int, np.ndarray]:
        if self.breaker.allows():
            try:
                faults.fire("ec.backend.device.reconstruct")
                out = self.primary.reconstruct(shards, want=want)
                self.breaker.record_success()
                return out
            except Exception as e:
                self._device_failed("reconstruct", e)
        self.fallback_batches += 1
        return self.fallback.reconstruct(shards, want=want)

    # -- staged pipeline --------------------------------------------------
    #
    # to_device handles are (host_batch, device_handle|None); dispatched
    # handles are (kind, host_batch, device_result|None, coeffs|None) so
    # to_host knows WHICH computation to replay on CPU when the device
    # dies between dispatch and drain — encode_staged batches re-encode,
    # apply_staged batches re-apply the same coefficients, both
    # bit-identical to what the device would have produced.

    def to_device(self, data: np.ndarray):
        trace.lap("stage")  # the host copy, where there is one
        data = np.ascontiguousarray(data, dtype=np.uint8)
        if self.breaker.allows():
            try:
                faults.fire(
                    "ec.backend.device.to_device",
                    width=data.shape[1], chip=self.chip_label,
                )
                return (data, self.primary.to_device(data))
            except Exception as e:
                self._device_failed("to_device", e)
        return (data, None)

    def encode_staged(self, staged):
        host, dev = staged
        if dev is not None:
            try:
                faults.fire(
                    "ec.backend.device.encode_staged", chip=self.chip_label
                )
                return ("encode", host, self.primary.encode_staged(dev), None)
            except Exception as e:
                self._device_failed("encode_staged", e)
        return ("encode", host, None, None)

    def apply_staged(self, coeffs: np.ndarray, staged):
        host, dev = staged
        coeffs = np.ascontiguousarray(coeffs, dtype=np.uint8)
        if dev is not None:
            try:
                faults.fire(
                    "ec.backend.device.apply_staged", chip=self.chip_label
                )
                return (
                    "apply", host, self.primary.apply_staged(coeffs, dev), coeffs
                )
            except Exception as e:
                self._device_failed("apply_staged", e)
        return ("apply", host, None, coeffs)

    def to_host(self, result) -> np.ndarray:
        kind, host, dev, coeffs = result
        if dev is not None:
            try:
                faults.fire("ec.backend.device.to_host", chip=self.chip_label)
                out = np.asarray(self.primary.to_host(dev), dtype=np.uint8)
                self.breaker.record_success()
                return out
            except Exception as e:
                self._device_failed("to_host", e)
        # Mid-batch failover: the host copy recomputes on CPU,
        # bit-identical to what the device would have produced.
        self.fallback_batches += 1
        if kind == "apply":
            return self.fallback.apply(coeffs, host)
        return self.fallback.encode(host)


@functools.lru_cache(maxsize=16)
def get_backend(name: str, data_shards: int, parity_shards: int) -> RSBackend:
    """name: cpu | tpu | auto. 'auto' means the TPU when this process
    has one (utils/devices.py), wrapped in the CPU-fallback shim so a
    device that dies mid-stream degrades to the (bit-identical) CPU
    path instead of failing the encode, and the CPU when it has none.
    A device backend that fails to CONSTRUCT on a TPU host raises."""
    ctx = ECContext(data_shards, parity_shards)
    if name == "cpu":
        return CpuBackend(ctx)
    if name == "tpu":
        return JaxBackend(ctx)
    if name == "auto":
        from ..utils.devices import tpu_attached

        if tpu_attached():
            return FallbackBackend(JaxBackend(ctx), CpuBackend(ctx))
        return CpuBackend(ctx)
    raise ECError(f"unknown EC backend {name!r} (want cpu|tpu|auto)")
