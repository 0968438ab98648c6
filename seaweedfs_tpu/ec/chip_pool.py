"""Pod-level stream placement: route EC streams to chips, not slices.

PR 4's scheduler treats one backend instance as one chip, so on the
column-mesh backend EVERY stream is sliced across all local devices and
the whole pod serializes behind a single admission queue. The reference
gets its throughput from many independent volume workers
(weed/storage/erasure_coding), not one wide one; the TPU-native
analogue is stream-level data parallelism — when concurrent EC streams
outnumber chips, place WHOLE streams on single chips and reserve
column-mesh slicing for the lone-wide-stream case. Outputs are
bit-identical either way: the mesh path is bit-exact vs the
single-device path by construction (parity is columnwise-independent),
so placement is purely a scheduling decision.

Pieces
------

- :class:`ChipBackend` — a single-device JaxBackend pinned to one local
  device (`jax.device_put(…, device)`; jit follows the committed input,
  so every staged dispatch runs on that chip).
- :class:`ChipPool` — one per mesh-capable backend, built lazily from
  the mesh's own device list. Each chip's backend is
  constructed on first use; when the pooled backend is a
  FallbackBackend, every chip gets its OWN FallbackBackend + breaker,
  so one chip dying fails over only ITS streams to CPU while siblings
  keep their chips (the shared CpuBackend is stateless).
- :func:`place_stream` — the policy: route each new DeviceStream to the
  chip with the least outstanding placed cost (deterministic: ties go
  to the lowest chip index), falling back to the column-mesh backend
  only when the stream is explicitly wide AND no other stream is placed
  (mode "auto"), or always ("mesh"), or never ("chip") — the
  `ec_placement` knob, per QueueScope.

The wide/mesh path a stream keeps here is the POD-SHARDED encode since
the data-gravity PR: `parallel.MeshRS` lowers the XLA impl through one
explicit `NamedSharding`/pjit computation over the full device mesh
with the stripe (column) axis constrained (`SEAWEED_EC_POD_PJIT`),
which on multi-process TPU pods spans every process's devices — the
per-process shard_map wrapper remains for the Pallas impls. Placement
span events record which lowering the mesh decision landed on
(`pod_sharded`).

The pool itself is process-wide (chips are physical; two tenant scopes
sharing a host should see each other's load), while each scope gets its
own per-chip DeviceQueues (config isolation, `device_queue.QueueScope`).

The residency nuance recorded here since PR 5 — a chip serving a wide
MESH stream beside chip-placed streams could transiently hold two
windows of in-flight batches — is closed by the process-wide
ResidencyLedger (ec/device_queue.py): every queue charges the physical
chip(s) in a second admission phase, and a mesh-wide batch charges a
slot on EVERY chip it spans, so the per-chip budget holds across
queues and scopes. Routing reads the ledger too: `_live_loads_for`
adds each chip's CROSS-SCOPE in-flight cost on top of the scope's own
queue view, so another tenant's load repels placement (the PR 14
carried item).
"""

from __future__ import annotations

import threading
import weakref

import numpy as np

from .device_queue import QueueScope, resolve_scope
from .backend import CpuBackend, FallbackBackend, JaxBackend
from ..utils import metrics as _M
from ..utils.retry import CircuitBreaker

# An open breaker means this chip's streams are failing over to CPU:
# routing treats it as carrying this much extra outstanding cost, so a
# healthy sibling wins any remotely close call while a dead pod (all
# breakers open) still degrades gracefully instead of refusing.
BREAKER_OPEN_PENALTY = 1 << 40

_placement_decisions = _M.REGISTRY.counter(
    "sw_ec_placement_decisions_total",
    "EC stream placement decisions by the load signal that drove them "
    "(live = per-chip DeviceQueue.load() moved the pick, ledger = "
    "static stream cost hints alone, mesh = column-sliced)",
    ("signal",),
)


class ChipBackend(JaxBackend):
    """Single-device JaxBackend pinned to one local device.

    Staged H2D (the inherited `to_device`) puts to `self.device`;
    computation follows the committed input, so encode_staged/apply_staged run on
    exactly this chip. The synchronous surface (encode/apply without
    staging) is only used by CPU fallback replays and inherits the
    default-device behavior — streams always take the staged path.

    Construction bypasses JaxBackend.__init__: the chips of one pool
    SHARE one RSJax codec (`rs` — jit dispatch follows the committed
    input's device, and the coeff/bit-matrix caches are lock-protected
    since PR 4), so an 8-chip pool does not pay 8 identical bit-matrix
    constructions."""

    def __init__(self, ctx, device, rs=None, impl: str = "xla",
                 interpret: bool = False):
        from .backend import _BackendBase

        _BackendBase.__init__(self, ctx)
        if rs is None:
            from ..ops.rs_jax import RSJax

            rs = RSJax(
                ctx.data_shards, ctx.parity_shards,
                impl=impl, interpret=interpret,
            )
        self._rs = rs
        self._mesh_rs = None  # this backend IS one chip
        self.device = device
        self.chip_label = f"{device.platform}:{device.id}"


class _PodLedger:
    """Shared load/stream accounting for one PHYSICAL pod.

    Pools are per backend instance (their chip backends are ctx- and
    wrapper-specific), but the chips are physical: two backends over
    the same devices (e.g. 10+4 and 5+2 volumes — get_backend caches
    them separately) must see each OTHER's placed streams, or both
    would route their heavy streams to "idle" chip 0 while the rest of
    the pod sits empty. `pool_for` shares one ledger per device set."""

    def __init__(self, n: int):
        self.lock = threading.Lock()
        self.load: list[int] = [0] * n
        self.streams: list[int] = [0] * n


class ChipPool:
    """Per-chip backends + least-loaded stream routing for one pod.

    `devices` is any sequence of placement targets and `make_chip(dev)`
    builds the backend for one of them — the routing/load core is
    plain Python (tests/test_chip_placement.py exercises it without jax).

    Load accounting is per placed STREAM: `acquire(cost_hint)` charges
    the stream's estimated total cost (rows x bytes it will dispatch)
    to the chosen chip until the returned release fires. Routing is
    deterministic given the arrival order: least outstanding cost,
    ties to the lowest chip index. The accounting lives in a
    `_PodLedger` that `pool_for` SHARES between pools over the same
    physical devices."""

    def __init__(self, devices, make_chip, labels=None, ledger=None):
        self.devices = list(devices)
        self._make_chip = make_chip
        self.labels = (
            list(labels)
            if labels is not None
            else [str(d) for d in self.devices]
        )
        self._ledger = ledger if ledger is not None else _PodLedger(
            len(self.devices)
        )
        self._lock = self._ledger.lock
        self._chips: list = [None] * len(self.devices)

    @property
    def n_chips(self) -> int:
        return len(self.devices)

    def chip_backend(self, i: int):
        """The backend for chip `i`, constructed lazily OUTSIDE the
        pod lock (RSJax construction is host-side numpy work, but it
        must never serialize concurrent placements or stream-close
        releases). Two racers may both build; the insert keeps one."""
        with self._lock:
            be = self._chips[i]
        if be is None:
            built = self._make_chip(self.devices[i])
            with self._lock:
                be = self._chips[i]
                if be is None:
                    be = self._chips[i] = built
        return be

    def loads(self) -> list[int]:
        with self._lock:
            return list(self._ledger.load)

    def idle(self) -> bool:
        """True when no stream is placed on any chip of the POD (any
        pool sharing this ledger counts)."""
        with self._lock:
            return not any(self._ledger.streams)

    def _release_fn(self, indices, hint):
        done = [False]
        led = self._ledger

        def release() -> None:
            with led.lock:
                if done[0]:
                    return
                done[0] = True
                for j in indices:
                    led.load[j] -= hint
                    led.streams[j] -= 1

        return release

    def acquire(
        self,
        cost_hint: int = 0,
        prefer_mesh: bool = False,
        force_mesh: bool = False,
        live_loads: "list[int] | None" = None,
    ):
        """Place one stream: returns (chip_index, backend, release).
        `release()` is idempotent and must fire when the stream closes
        (success or death) so the chip's load drains.

        `live_loads` (per chip index, same order as `devices`) is the
        LIVE routing signal: each chip's DeviceQueue cost units
        queued+in-flight right now (plus breaker penalties), ADDED to
        the ledger's static placed-cost charges when ranking chips —
        the ROADMAP "routing reads live load" loop. The sum is
        deliberately conservative: a chip busy with work the ledger
        never saw (one-shot gateway admissions, another scope's
        dispatches) now repels new streams, while a placed stream
        keeps its ledger charge until it closes, so its own in-flight
        batches count twice while it is actively dispatching — routing
        prefers a chip that is merely RESERVED over one that is
        reserved AND busy, which is the right bias even though it
        overstates absolute load.

        `prefer_mesh` takes the whole-pod mesh IFF the pod is idle,
        decided under the SAME lock as the charge (no
        check-then-acquire window for a racing placement to slip
        through): chip_index and backend come back None and EVERY chip
        is charged the hint — a column-sliced stream occupies the whole
        pod, so pool.idle() reads False and a second stream (wide or
        not) routes to a chip instead of stacking behind the mesh
        queue. `force_mesh` charges the whole pod unconditionally (a
        pinned `ec_placement=mesh` stream runs column-sliced regardless
        of load, but must still be VISIBLE to every other scope's
        routing and idle checks)."""
        hint = max(int(cost_hint), 1)
        led = self._ledger
        live = live_loads if live_loads is not None else [0] * len(
            self.devices
        )
        with self._lock:
            if force_mesh or (prefer_mesh and not any(led.streams)):
                indices = range(len(led.load))
                i = None
            else:
                i = min(
                    range(len(led.load)),
                    key=lambda j: (led.load[j] + live[j], j),
                )
                indices = (i,)
            for j in indices:
                led.load[j] += hint
                led.streams[j] += 1
            release = self._release_fn(indices, hint)
        if i is None:
            return None, None, release
        try:
            be = self.chip_backend(i)
        except BaseException:
            # The charge landed before lazy construction; a failed
            # build must not leave phantom load on the pod ledger.
            release()
            raise
        return i, be, release


# --------------------------------------------------------------------------
# Pool registry: one pool per mesh-capable backend instance (its chips
# are ctx-specific), with the load LEDGER shared per physical device
# set — pools over the same chips route against one load state.
# --------------------------------------------------------------------------

_pools_lock = threading.Lock()
_pools: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
# device-identity -> _PodLedger; device sets are process-stable, so a
# plain dict (bounded by distinct pod topologies, in practice 1) is fine
_ledgers: dict = {}


def pool_for(backend) -> ChipPool | None:
    """The chip pool behind `backend`, or None when it is not a
    multi-device (column-mesh) backend. Its chips are the devices of
    the backend's own mesh."""
    if backend is None:
        return None
    primary = getattr(backend, "primary", backend)
    mesh_rs = getattr(primary, "_mesh_rs", None)
    if mesh_rs is None or mesh_rs.n_devices < 2:
        return None
    with _pools_lock:
        pool = _pools.get(backend)
        if pool is None:
            devices = list(np.ravel(mesh_rs.mesh.devices))
            ctx = backend.ctx
            rs = primary._rs
            wrap = isinstance(backend, FallbackBackend)
            cpu = CpuBackend(ctx) if wrap else None
            # Plain values only: capturing `backend` itself would pin
            # the WeakKeyDictionary key via its own pool value, leaking
            # every mesh backend (+ chips/queues) for process lifetime.
            brk_threshold = backend.breaker.failure_threshold if wrap else 0
            brk_timeout = backend.breaker.reset_timeout if wrap else 0.0

            def make_chip(dev):
                chip = ChipBackend(ctx, dev, rs=rs)
                if not wrap:
                    return chip
                # Per-chip breaker: one chip's repeated deaths demote
                # only ITS streams to CPU; siblings keep their chips.
                # A fresh instance per chip, but with the POOLED
                # backend's thresholds — an embedder's tolerance config
                # must survive the reroute onto chips.
                # (FallbackBackend copies chip_label from its primary.)
                return FallbackBackend(chip, cpu, breaker=CircuitBreaker(
                    failure_threshold=brk_threshold,
                    reset_timeout=brk_timeout,
                ))

            labels = [f"{d.platform}:{d.id}" for d in devices]
            # one load ledger per PHYSICAL device set: a second backend
            # over the same chips (another shard ratio) routes against
            # the same load state instead of a blind private copy
            led_key = tuple(labels)
            ledger = _ledgers.get(led_key)
            if ledger is None:
                ledger = _ledgers[led_key] = _PodLedger(len(devices))
            pool = ChipPool(devices, make_chip, labels=labels, ledger=ledger)
            _pools[backend] = pool
    return pool


class Placement:
    """One stream's resolved (backend, queue) pair. `chip` is the chip
    index (None = the original backend: mesh slicing, or no pool).
    close() releases the chip-load charge; idempotent."""

    __slots__ = ("backend", "queue", "chip", "_release")

    def __init__(self, backend, queue, chip=None, release=None):
        self.backend = backend
        self.queue = queue
        self.chip = chip
        self._release = release

    def close(self) -> None:
        if self._release is not None:
            rel, self._release = self._release, None
            rel()

    def __enter__(self) -> "Placement":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def chip_load_hint(scope: QueueScope | None = None) -> dict[str, dict]:
    """Read-only per-chip load/breaker hint: {chip_label: {"load":
    outstanding cost units queued+in-flight, "breaker":
    ""|"closed"|"open"|...}}.

    This is the LIVE routing signal: `place_stream` ranks chips by
    ledger charge PLUS this load (and ships it to the master via
    heartbeats for cluster-wide placement — /cluster/status,
    sw_ec_queue_load, `placement.NodeView.ec_load`). Reads only the
    scope's existing DeviceQueues — no queue is created and no jax/
    device state is touched."""
    return resolve_scope(scope).queue_loads()


def _pod_sharded(backend) -> bool:
    """True when a mesh-kept stream's encode runs the explicit
    NamedSharding/pjit pod lowering (parallel.MeshRS.pod_sharded)."""
    primary = getattr(backend, "primary", backend)
    return bool(
        getattr(getattr(primary, "_mesh_rs", None), "pod_sharded", False)
    )


def _live_loads_for(pool: ChipPool, scope: QueueScope) -> list[int]:
    """Per-chip-index live load aligned with `pool.labels`: the scope's
    own DeviceQueue.load() (queued + in-flight) plus the residency
    ledger's CROSS-SCOPE share (every other scope's — and the mesh
    path's — in-flight cost on the chip) plus the breaker penalty.
    The scope's own in-flight cost is subtracted from the ledger view
    so it is never counted twice. Chips with no state anywhere read
    0 — never create a queue just to ask its load."""
    hint = scope.queue_loads()
    shared = scope.residency_loads()
    out = []
    for label in pool.labels:
        h = hint.get(label)
        load = 0
        own_inflight = 0
        if h is not None:
            load = int(h.get("load", 0))
            own_inflight = int(h.get("inflight_cost", 0))
        load += max(int(shared.get(label, 0)) - own_inflight, 0)
        if h is not None and h.get("breaker") == "open":
            load += BREAKER_OPEN_PENALTY
        out.append(load)
    return out


def place_stream(
    backend,
    priority: str,
    *,
    scope: QueueScope | None = None,
    cost_hint: int = 0,
    wide: bool = False,
    span=None,
) -> Placement:
    """Resolve where one new EC stream runs.

    Returns a Placement whose `.backend` the producer must use for
    to_device/…_staged/to_host and whose `.queue` its DeviceStream
    opens on (None = scheduler disabled: the PR 3 private window).
    The caller MUST close() the placement when the stream ends.

    Policy (scope's `ec_placement`):

    - "mesh": always the original backend (PR 4 behavior — every
      stream column-sliced across the pod behind one queue).
    - "chip": always route to the least-loaded chip of the pool.
    - "auto" (default): route to a chip, EXCEPT an explicitly `wide`
      stream arriving at an idle pod, which keeps the whole mesh
      (lone huge encode: slicing wins when nothing competes).

    No pool (single device, CPU backend, scheduler disabled) degrades
    to the original backend + its scope queue — exactly PR 4.
    `priority` does not influence routing (the per-chip queue enforces
    class policy); it is accepted so call sites read naturally and for
    future affinity policies.

    `span` (utils/trace.py; None = tracer disarmed) records the routing
    decision as a "placement" event carrying the pod load ledger the
    decision saw — the evidence for "why did this stream land on chip
    3" when reading a trace."""
    scope = resolve_scope(scope)
    if backend is None or not scope.enabled:
        # Scheduler disabled (or no backend): no pool routing either —
        # placement is a layer ON TOP of the per-chip queues. The mesh
        # queue itself is resolved lazily on the paths that USE it: a
        # chip-routed stream must not register a phantom mesh queue in
        # stats/metrics.
        return Placement(backend, None)
    mode = scope.placement
    pool = pool_for(backend)
    if mode == "mesh":
        if pool is None:
            return Placement(backend, scope.for_backend(backend))
        # Pinned mesh still charges the whole pod: another scope's
        # auto-wide placement must see this pod as busy, not stack a
        # second column-sliced stream through an independent window.
        if span is not None:
            span.event(
                "placement", mode=mode, chip="mesh", signal="mesh",
                loads=pool.loads(), cost_hint=cost_hint, wide=wide,
                queue_load_hint=chip_load_hint(scope),
                pod_sharded=_pod_sharded(backend),
            )
        _placement_decisions.inc(signal="mesh")
        _, _, release = pool.acquire(cost_hint, force_mesh=True)
        return Placement(backend, scope.for_backend(backend), None, release)
    if pool is None or pool.n_chips < 2:
        return Placement(backend, scope.for_backend(backend))
    # Routing inputs, snapshotted BEFORE the charge: the pod ledger
    # (static per-stream cost hints) PLUS the live per-chip queue load
    # (cost units queued+in-flight right now, breaker-penalized) — the
    # decision follows their SUM, so a chip busy with work the ledger
    # never saw repels new streams and a hinted-but-drained stream
    # stops repelling them.
    live = _live_loads_for(pool, scope)
    signal = "live" if any(live) else "ledger"
    loads_seen = pool.loads() if span is not None else None
    idx, chip_be, release = pool.acquire(
        cost_hint, prefer_mesh=(wide and mode == "auto"),
        live_loads=live,
    )
    if span is not None:
        # the evidence for "why did this stream land on chip 3": the
        # ledger AND the live queue loads the decision read, and which
        # signal source was decisive
        span.event(
            "placement", mode=mode,
            chip=("mesh" if idx is None else pool.labels[idx]),
            signal=("mesh" if idx is None else signal),
            loads=loads_seen, live_loads=live,
            cost_hint=cost_hint, wide=wide,
            queue_load_hint=chip_load_hint(scope),
            pod_sharded=(idx is None and _pod_sharded(backend)),
        )
    _placement_decisions.inc(signal=("mesh" if idx is None else signal))
    if idx is None:
        # Lone wide stream on an idle pod: it keeps the whole mesh and
        # the charge on every chip makes the pod read busy, so a second
        # arrival (wide or not) routes to a chip instead of stacking a
        # second column-sliced stream behind the same mesh queue.
        return Placement(backend, scope.for_backend(backend), None, release)
    try:
        chip_queue = scope.for_backend(chip_be)
    except BaseException:
        release()
        raise
    return Placement(chip_be, chip_queue, idx, release)
