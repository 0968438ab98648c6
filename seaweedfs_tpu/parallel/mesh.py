"""Device-mesh helpers for the distributed EC compute path.

EC encode is embarrassingly parallel over the COLUMN (block) dimension:
parity is columnwise-independent, so the natural TPU sharding is data
parallelism over blocks with the (8m x 8k) bit-matrix replicated on
every chip; XLA inserts no collectives for the encode itself, and
cross-device traffic appears only in optional global reductions (the
verify checksum psum) — mirroring how the reference only ever shares
per-shard CRCs between encoder workers, never shard bytes
(weed/storage/erasure_coding).

These helpers back both the production `JaxBackend` (multi-device
encode in ec/backend.py) and the driver's `dryrun_multichip`.
"""

from __future__ import annotations

import os
import threading

import numpy as np

BLOCK_AXIS = "blocks"

# The per-chip body may be a Pallas kernel: pallas_call declares no
# varying-axes type for its output, and in interpret mode its body mixes
# the replicated bit-matrix with the column slice, both of which
# shard_map's varying-axes check refuses. The bodies here are
# column-local (no collectives, no autodiff), which is all that check
# protects.
_CHECK_VMA = False


def make_mesh(n_devices: int | None = None, devices=None):
    """1-D mesh over local devices (default: all of them)."""
    import jax
    from jax.sharding import Mesh

    devs = list(devices if devices is not None else jax.devices())
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"need {n_devices} devices, have {len(devs)}"
            )
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (BLOCK_AXIS,))


def column_sharding(mesh):
    """(rows, cols) arrays sharded along cols — the EC block split."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(None, BLOCK_AXIS))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P())


def pad_cols(data: np.ndarray, multiple: int) -> tuple[np.ndarray, int]:
    """Zero-pad columns to a device multiple; returns (padded, orig_n).
    Parity of a zero column is zero, so padding never changes the
    parity of real columns (bit-exactness by construction)."""
    n = data.shape[1]
    rem = n % multiple
    if rem == 0:
        return data, n
    padded = np.zeros((data.shape[0], n + multiple - rem), dtype=data.dtype)
    padded[:, :n] = data
    return padded, n


def pod_pjit_mode() -> str:
    """SEAWEED_EC_POD_PJIT: "auto" (default — the explicit
    NamedSharding/pjit pod encode for the XLA impl, shard_map for the
    Pallas impls whose kernels GSPMD cannot partition), "1" (force
    pjit where traceable), "0" (always shard_map — the pre-gravity
    shape)."""
    return os.environ.get("SEAWEED_EC_POD_PJIT", "auto").strip().lower()


class MeshRS:
    """Reed-Solomon encode/reconstruct over a device mesh with column
    sharding. Bit-exact vs the single-device path: the column split is
    exact and the bit-matrix is replicated.

    Two encode lowerings, selected at construction:

    - **pod-sharded pjit** (XLA impl, the default via
      ``SEAWEED_EC_POD_PJIT=auto``): one ``jax.jit`` over the WHOLE
      mesh with explicit ``NamedSharding`` in/out shardings and a
      ``with_sharding_constraint`` pinning the stripe (block/column)
      axis — GSPMD partitions the bit-matmul itself, which on a
      multi-process TPU pod runs across every process's devices from
      one traced computation (SNIPPETS.md [2]: pjit on multi-process
      platforms), where per-process ``shard_map`` would stop at the
      process boundary. The matmul is columnwise-independent, so the
      partitioner inserts no collectives and the output is bit-exact.
    - **shard_map** (Pallas impls, or ``SEAWEED_EC_POD_PJIT=0``): each
      device runs the FULL single-chip path (fused Pallas kernel) on
      its column slice — the wrapper that works for every impl.
    """

    def __init__(self, rs, mesh):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        self.rs = rs
        self.mesh = mesh
        self.n_devices = mesh.devices.size
        # Physical identity of every chip one wide batch occupies, in
        # the same "<platform>:<id>" form chip_pool labels per-chip
        # backends with: the residency ledger (ec/device_queue.py)
        # charges a mesh-wide stream one slot on EACH of these, so a
        # wide stream can no longer admit past the per-chip budgets.
        self._device_labels = tuple(
            f"{d.platform}:{d.id}" for d in np.ravel(mesh.devices)
        )
        # jitted shard_map applies, keyed by (m_out, k): the decode
        # coefficient SHAPE is stable per shard-loss set, so each key
        # compiles once and the bit-matrix rides in as a replicated arg.
        # Locked: the device-queue scheduler dispatches several streams'
        # threads into one MeshRS, and a get-or-compile race would
        # compile the same shape twice (wasted minutes on a real mesh).
        self._apply_jits: dict = {}
        self._apply_jits_lock = threading.Lock()
        self._repl = replicated(mesh)
        self._cols = column_sharding(mesh)

        mode = pod_pjit_mode()
        # pjit needs the encode traceable as ordinary jnp ops so GSPMD
        # can partition it; the XLA bit-matmul is, the Pallas kernels
        # are opaque calls — those keep the per-device shard_map.
        self.pod_sharded = mode != "0" and (
            getattr(rs, "impl", "xla") == "xla" or mode == "1"
        )
        if self.pod_sharded:
            cols = self._cols

            def _pod_encode(d):
                # explicit stripe-axis constraint INSIDE the jit: even
                # if XLA would re-layout intermediates, the output
                # parity stays column-sharded exactly like the input —
                # the next pipeline stage (D2H drain) reads each chip's
                # slice without a gather.
                d = jax.lax.with_sharding_constraint(d, cols)
                return jax.lax.with_sharding_constraint(rs.encode(d), cols)

            self._encode = jax.jit(
                _pod_encode, in_shardings=cols, out_shardings=cols
            )
        else:
            # shard_map over the impl's own encode: each device runs
            # the FULL single-chip path (XLA bit-matmul or the fused
            # Pallas kernel) on its column slice.
            self._encode = jax.jit(
                shard_map(
                    rs.encode,
                    mesh=mesh,
                    in_specs=P(None, BLOCK_AXIS),
                    out_specs=P(None, BLOCK_AXIS),
                    check_vma=_CHECK_VMA,
                )
            )

    def device_labels(self) -> tuple[str, ...]:
        """Per-chip "<platform>:<id>" labels this mesh spans (residency
        charging keys — see ec/device_queue._residency_keys)."""
        return self._device_labels

    def put(self, data: np.ndarray):
        """H2D with column sharding (async). Caller pads columns to a
        device multiple first (see pad_cols)."""
        import jax

        return jax.device_put(np.ascontiguousarray(data), self._cols)

    def encode(self, staged):
        """Sharded parity dispatch; returns a device array handle."""
        return self._encode(staged)

    def _apply_jit(self, m_out: int, k: int):
        """The jitted shard_map apply for one (m_out, k) coefficient
        shape, built once."""
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        key = (int(m_out), int(k))
        with self._apply_jits_lock:
            fn = self._apply_jits.get(key)
        if fn is None:
            # Build OUTSIDE the lock: holding it across a minutes-long
            # mesh compile would block every other stream's already-
            # compiled applies — priority inversion on the foreground
            # path the device queue exists to protect. Two streams
            # racing the same new shape may both build; the insert
            # below keeps one, and jax.jit defers actual compilation
            # to first call anyway.
            rs = self.rs

            def _local(b, d):
                return rs._apply(b, d, m_out)

            fn = jax.jit(
                shard_map(
                    _local,
                    mesh=self.mesh,
                    in_specs=(P(), P(None, BLOCK_AXIS)),
                    out_specs=P(None, BLOCK_AXIS),
                    check_vma=_CHECK_VMA,
                )
            )
            with self._apply_jits_lock:
                fn = self._apply_jits.setdefault(key, fn)
        return fn

    def apply(self, bits: np.ndarray, staged, m_out: int):
        """General GF(256) apply over the column mesh: `bits` is the
        expanded (8*m_out x 8k) bit-matrix, replicated on every chip
        (like the parity matrix in encode), `staged` the column-sharded
        data. Column-independent like encode, so the split is bit-exact
        and no collectives appear. Returns a device handle (async)."""
        import jax.numpy as jnp

        fn = self._apply_jit(m_out, staged.shape[0])
        return fn(jnp.asarray(bits), staged)

    def global_checksum(self, sharded) -> int:
        """psum over the mesh of a uint32 sum — the cheap cross-device
        integrity reduction (rides ICI, never moves shard bytes)."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local_sum(x):
            return jax.lax.psum(jnp.sum(x.astype(jnp.uint32)), BLOCK_AXIS)

        return int(
            shard_map(
                local_sum,
                mesh=self.mesh,
                in_specs=P(None, BLOCK_AXIS),
                out_specs=P(),
            )(sharded)
        )
