"""Pipeline flight recorder: stage-attributed spans for the EC data path.

An EC operation is bound by its host side while the device kernel is
close to free (PERF.md section 5: the kernel is under 1 % of a
rebuild), and aggregate counters after the fact do not say where. This
module attributes wall time to every STAGE of an EC operation
(admission wait, queue wait, disk read, H2D dispatch, device drain,
fused write+CRC sink, verify, publish/rename) and stitches the stages
into one span tree per operation, across threads and — via gRPC
metadata — across servers.

Model
-----

- A :class:`Span` is one timed node: a root per EC op (``ec.encode``,
  ``ec.rebuild``, ``ec.decode``, ``ec.degraded_read``,
  ``ec.peer_rebuild``, ``rpc.ec_shard_read`` …), children for sub-ops
  (per-peer fetches, the nested rebuild inside a decode). Spans carry
  per-stage ACCUMULATORS (total seconds + count + CPU seconds per stage
  name) rather than one child span per pipeline batch.
- Beside the accumulators every stage entry leaves an INTERVAL
  ``(stage, start_ns, end_ns, thread, cpu_ns)`` on the span's own
  monotonic clock (``time.perf_counter_ns``), at most
  :data:`MAX_INTERVALS` per span (past the cap only the accumulators
  grow and ``stages_dropped`` counts). Intervals say which thread sets
  the pace where stages overlap: the reader's ``disk_read`` beside the
  sink's ``device_drain``. An after-the-fact ``add_stage(name, secs)``
  leaves ``(now - secs, now)`` with no CPU reading.
- Spans and ``with``-scoped stages read ``time.thread_time_ns`` at both
  ends and export ``cpu_s``: wall less CPU is time the thread did not
  run (blocked on a read, the device, a lock, or waiting for the GIL).
- Spans and ``with``-scoped stages also open a
  ``jax.profiler.TraceAnnotation`` (``sw:<op>`` / ``sw:<op>/<stage>``,
  the trace id as an argument) where JAX is already loaded, so a
  profiler trace taken by anyone holds the program's stages on the
  device operations' clock. JAX is never imported for this.
- A SUB-STAGE ``<stage>.<part>`` (:data:`SUB_STAGES`) splits its parent
  from inside: :func:`lap` begins one under whatever stage the calling
  thread has open and ends the one before it, so the backend marks its
  parts without being handed a span, and the parts of one entry lie end
  to end. Every total that sums stages counts parents only.
- Completed LOCAL ROOTS (spans with no local parent — including spans
  whose parent lives on another server) land in a bounded ring,
  dumpable as Chrome ``trace_event`` JSON (``/debug/traces``; load
  the file in Perfetto / chrome://tracing).
- Trace identity crosses RPC hops in gRPC metadata
  (:data:`TRACE_ID_KEY` / :data:`PARENT_SPAN_KEY`) alongside
  ``X-Request-ID``, so a fleet-dispatched peer-fetch rebuild yields ONE
  trace id spanning master task → rebuilding holder → every peer's
  shard-read stream.

- While armed, two WAIT PROBES (``utils/interp_probe.py``, started and
  stopped by :func:`configure`) measure what no span can: how long a
  thread that is ready to run waits for the interpreter, how long for a
  core, and which class of thread burnt the CPU. Every 100 ms they close
  a root span :data:`PROBE_OP`, kept in a ring of its own. The native
  calls of ``utils/native.py`` that do real work book what their return
  to the interpreter cost (:func:`book_return`: ``interp_wait_ns``,
  ``interp_returns``), ``recv_into`` and ``send_file``, which carry every
  byte of a read from a peer, among them.

Canonical stage names (the Prometheus ``stage`` label of
``sw_ec_stage_seconds``):

=================  =====================================================
``admission_wait`` blocked in the device-queue scheduler before dispatch
``queue_wait``     blocked on a full bounded pipeline queue
                   (backpressure; accumulated from BOTH pipeline
                   threads, so its total may exceed the op wall)
``disk_read``      source reads (shards, .dat) in the reader thread
``sibling_read``   degraded-read sibling shard reads (local + remote)
``h2d_dispatch``   host→device upload + async kernel dispatch
``device_drain``   blocked in ``to_host`` (device compute not yet hidden
                   + D2H)
``write_sink``     fused write+CRC sink appends (or plain output writes)
``crc_verify``     sidecar CRC verification of streamed/reconstructed
                   bytes
``verify``         dedicated whole-shard sidecar verification passes
``reconstruct``    synchronous (non-staged) Reed-Solomon apply
``fsync_publish``  flush/fsync/rename publication windows
``stream``         server-side RPC response streaming
``fetch_queue``    a read from a peer (``ec.peer_read``): a reconstruction's
                   fetch handed to the fetch pool, until its thread runs
``conn_checkout``  ... a connection to the holder's shard plane taken from
                   the pool, or dialled
``request_rtt``    ... the request sent, until the answer's header is
                   parsed (over the stream: until the first chunk)
``payload_land``   ... the range landing in the buffer it is used in
``ready_wait``     HTTP: a readable connection queued for a pool worker
                   (ends where its span starts)
``parse``          HTTP: request line and headers
``send``           HTTP: the response body leaving (``send_body``)
=================  =====================================================

Sub-stages: ``h2d_dispatch`` = ``.stage`` (contiguous host copy /
column padding) + ``.put`` (``jax.device_put``) + ``.launch`` (the
jitted apply: coefficient bits, cache look-up, enqueue);
``device_drain`` = ``.ready`` (blocked until the result exists on the
device: upload, kernel, device queue) + ``.d2h`` (what is left of the
copy home, which the launch asked for, into a numpy array)
+ ``.host_copy`` (``np.ascontiguousarray`` where it copies);
``reconstruct`` (single-shot degraded read) = ``.put`` (the sibling
matrix's one ``device_put``) + ``.launch`` + ``.ready`` + ``.d2h``;
``volume.read`` (a needle GET of an EC volume) = ``.index`` (the
``.ecx`` look-up under the volume's lock) + ``.shard`` (intervals read
from mounted shards or a peer) + ``.recover`` (intervals recovered: the
``ec.degraded_read`` child span lies inside) + ``.parse`` (the join and
``Needle.from_bytes``, the body's CRC); ``stream`` of an
``rpc.ec_shard_read`` = ``.resolve`` (the fault point, the volume, the
generation fence, the shard's file) + ``.header`` (the shard plane's
response header leaving) + ``.sendfile`` (the range leaving: ``sendfile``
or the Python egress loop).

The four stages of an ``ec.peer_read`` lie END TO END on their span and
add up to it: the reader opens the first one with its span and whoever
does the next step turns (:func:`turn`) the thread's open stage into the next
at one clock reading (``ec/net_plane.py`` is handed no span).

Overlap efficiency
------------------

Per completed root, over the WHOLE span tree: let ``device`` be the
summed device-stage time (``h2d_dispatch`` + ``device_drain``),
``host`` the summed non-device stage time, and ``wall`` the root span
duration. Wall time not explained by host stages must have been spent
exposed to device work — and time measurably blocked in ``to_host``
(``device_drain``) is exposed by definition, which keeps the number
honest when host stages overlap EACH OTHER across pipeline threads
(their sum can exceed wall, zeroing the residue)::

    exposed = clamp(max(wall - host, drain), 0, device)
    overlap_efficiency = (device - exposed) / device

1.0 = every device second hid behind I/O (PR 3's staging is doing its
job on this host); 0.0 = fully serial. Exported per op class as
``sw_ec_overlap_efficiency`` — the single number that says whether the
staged pipeline actually overlaps.

Disarm discipline (same as ``faults/``): the tracer is OFF by default
and every production call site is a single module-bool (or is-None)
check when disarmed — no allocation, no lock, no contextvar read, no
clock read. Hot per-batch helpers (:func:`stage`, :func:`lap`,
:func:`count`, :func:`add_stage`, :func:`current`) take only positional
arguments so the disarmed path cannot even box a kwargs dict.
"""

from __future__ import annotations

import sys
import threading
import time
import uuid
from collections import deque
from contextvars import ContextVar

from . import metrics as _M
from . import request_id as _rid
from .glog import logger

_log = logger("trace")

# gRPC metadata keys (lowercase: gRPC normalizes ASCII keys).
TRACE_ID_KEY = "x-sw-trace-id"
PARENT_SPAN_KEY = "x-sw-parent-span"
REQUEST_ID_KEY = "x-request-id"

# The SAME trace identity over HTTP: the gateway hops (client → S3 →
# filer → volume) carry these beside X-Request-ID, so one S3 GET yields
# ONE trace id across every server it crosses. Canonical casing for
# send; HTTP header lookup is case-insensitive on receive.
TRACE_ID_HEADER = "X-Sw-Trace-Id"
PARENT_SPAN_HEADER = "X-Sw-Parent-Span"

DEFAULT_RING = 256
# Ring is additionally bounded by TOTAL SPAN COUNT across all retained
# trace docs: one span-heavy op class (a wide gateway fan-out op can
# carry hundreds of child spans) must not pin an unbounded share of
# memory behind a trace-count-only bound.
DEFAULT_RING_SPANS = 20_000

# Canonical stage names — the ONLY values legal as the `stage` label of
# ``sw_ec_stage_seconds``. tests/test_trace.py lints every stage literal
# in the package against this registry, so a typo'd label fails tier-1
# instead of silently forking a histogram series.
STAGES = frozenset({
    # device-queue / pipeline (PR 4-7)
    "admission_wait", "queue_wait", "disk_read", "stage_batch",
    "sibling_read", "h2d_dispatch", "device_drain", "write_sink",
    "crc_verify", "verify", "reconstruct", "fsync_publish", "stream",
    "index_sort", "peer_fetch",
    # an EC read's wait for bytes of a shard that a peer holds
    # (ec/ec_volume.py: an interval of a needle, a reconstruction's rows)
    "peer_read",
    # one such read, on its own span `ec.peer_read`: see TURN_STAGES
    "fetch_queue", "conn_checkout", "request_rtt", "payload_land",
    # leaf repair (PR 8)
    "repair_patch", "repair_fetch",
    # streaming EC (PR 14): incremental parity math + delta pwrites
    "parity_update",
    # gateway read path (PR 9): where a slow S3 GET burned its budget
    "s3.auth", "filer.lookup", "chunk.fetch", "volume.read",
    # HTTP front end (utils/http_pool.py, utils/request_id.py)
    "ready_wait", "parse", "send",
})

# parent stage -> its parts; a sub-stage lies inside its parent's
# interval, so every sum over stages skips SUB_STAGES
_SUB_PARTS = {
    "h2d_dispatch": ("stage", "put", "launch"),
    "device_drain": ("ready", "d2h", "host_copy"),
    "reconstruct": ("put", "launch", "ready", "d2h"),
    # an EC needle read (ec/ec_volume.py), under the HTTP handler's stage
    "volume.read": ("index", "shard", "peer", "recover", "parse"),
    # the holder's side of a read from a peer (ec/net_plane.py
    # ShardNetPlane._serve_one, the VolumeEcShardRead servicer)
    "stream": ("resolve", "header", "sendfile"),
}
_SUB_NAME = {
    (parent, part): f"{parent}.{part}"
    for parent, parts in _SUB_PARTS.items() for part in parts
}
SUB_STAGES = frozenset(_SUB_NAME.values())
STAGES = STAGES | SUB_STAGES

# Stages that turn() may end and begin: the steps of one read from a peer,
# which lie end to end on its `ec.peer_read` span. Any other open stage
# (a rebuild's `peer_fetch` around the same client calls) is left alone.
TURN_STAGES = frozenset({"conn_checkout", "request_rtt", "payload_land"})

# Intervals kept per span; a (10, 16 MiB)-batch rebuild of 1 GiB leaves
# about a hundred. 5 ints and a name each: some 100 KiB a span at most.
MAX_INTERVALS = 1024

# Stages that count as device time for the overlap-efficiency gauge.
DEVICE_STAGES = frozenset({"h2d_dispatch", "device_drain"})

_stage_seconds = _M.REGISTRY.histogram(
    "sw_ec_stage_seconds",
    "per-stage wall time of EC operations (tracer armed only)",
    ("op", "stage", "chip"),
)
_overlap_eff = _M.REGISTRY.gauge(
    "sw_ec_overlap_efficiency",
    "device time hidden behind I/O / total device time, per op class "
    "(latest completed trace)",
    ("op",),
)
# what crossed the host/device seam, counted where it crosses
# (ec/backend.py) under whatever stage is open: see count()
_seam_counters = {
    "h2d_bytes": _M.REGISTRY.counter(
        "sw_ec_h2d_bytes_total",
        "bytes put to the device by EC operations (tracer armed only)",
        ("op",),
    ),
    "d2h_bytes": _M.REGISTRY.counter(
        "sw_ec_d2h_bytes_total",
        "bytes fetched from the device by EC operations (tracer armed only)",
        ("op",),
    ),
    "d2h_dense_bytes": _M.REGISTRY.counter(
        "sw_ec_d2h_dense_bytes_total",
        "of sw_ec_d2h_bytes_total, the bytes that came home as dense 32-bit "
        "words (a uint8 result crosses with its sublane holes)",
        ("op",),
    ),
    "read_bytes": _M.REGISTRY.counter(
        "sw_ec_read_bytes_total",
        "bytes the encode and rebuild pipelines' readers landed in staged "
        "batches (tracer armed only)",
        ("op",),
    ),
    "read_reused_bytes": _M.REGISTRY.counter(
        "sw_ec_read_reused_bytes_total",
        "of sw_ec_read_bytes_total, the bytes that landed in a matrix the "
        "process-wide batch pool had held (pages mapped by an earlier batch)",
        ("op",),
    ),
    "batches": _M.REGISTRY.counter(
        "sw_ec_device_batches_total",
        "batches EC operations handed to the device (tracer armed only)",
        ("op",),
    ),
}

# Module-level fast-path flag, read unlocked by every instrumentation
# site. configure() flips it under _lock AFTER the ring/threshold are in
# place, so an armed reader never sees half-configured state; a racing
# reader at worst misses the first op after arming.
armed = False

_lock = threading.Lock()
_ring: deque = deque(maxlen=DEFAULT_RING)
_ring_spans = 0  # total span count across the retained docs
# The wait probes (utils/interp_probe.py) close a root span every 100 ms
# for as long as the tracer is armed: they get a ring of their own, of the
# same size, so that ten a second never push an operation's trace out.
PROBE_OP = "interp.probe"
_probe_ring: deque = deque(maxlen=DEFAULT_RING)
_max_ring_spans = DEFAULT_RING_SPANS
_slow_op_s = 0.0

# Per-(op, stage) exponentially-weighted moving averages of the seconds
# of the two DEVICE_STAGES (armed only, fed by Span._record). They ride
# volume-server heartbeats to the master, where ec/placement.py sums them
# into a node's `ec_stage_ewma_s`; no other stage has a reader, so no
# other stage entry takes the process-wide lock.
EWMA_ALPHA = 0.2
_ewma_lock = threading.Lock()
_stage_ewma: dict[tuple[str, str], float] = {}

_current: ContextVar["Span | None"] = ContextVar("sw_trace_span", default=None)
# the with-scoped stage this thread has open: the parent of lap()'s parts
_open_stage: ContextVar["_StageTimer | None"] = ContextVar(
    "sw_trace_stage", default=None
)

_trace_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _annotate(op: str, stage: str, trace_id: str):
    """An entered profiler annotation ``sw:<op>[/<stage>]``, or None
    where JAX is not loaded: this module never imports it."""
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        cls = _trace_annotation = getattr(prof, "TraceAnnotation", None)
        if cls is None:
            return None
    # A stable name, no ids in it: one name an op class and stage. The
    # id rides as an argument behind a letter: the profile's readers
    # take a bare "7144024763e54529" for a number, and show infinity.
    ann = cls(
        f"sw:{op}/{stage}" if stage else f"sw:{op}", trace_id=f"t{trace_id}"
    )
    ann.__enter__()
    return ann


class _Noop:
    """Singleton no-op context manager: the disarmed fast path of
    :func:`stage` and :func:`activate` returns this, so span-enter/exit
    when disarmed is one is-None check and zero allocations."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    # a stage timer's two knobs, accepted and ignored
    seconds = property(lambda self: None, lambda self, value: None)

    def drop(self) -> None:
        pass


_NOOP = _Noop()


class _StageTimer:
    """One with-scoped stage entry: wall and CPU clocks, a profiler
    annotation, and the thread's open stage for :func:`lap`. `seconds`
    overrides what the accumulator is charged (the interval stays the
    with-block's): ``admission_wait`` charges the queue's own
    ``ticket.wait_s``. :meth:`drop` records nothing."""

    __slots__ = (
        "span", "name", "chip", "seconds", "_dropped", "_t0", "_c0",
        "_ann", "_token", "_part", "_part_t0", "_part_c0", "_part_ann",
        "ended_ns",
    )

    def __init__(self, span: "Span", name: str, chip: str):
        self.span = span
        self.name = name
        self.chip = chip
        self.seconds = None
        self._dropped = False
        self._part = None
        self.ended_ns = 0  # the clock reading of __exit__

    def drop(self) -> None:
        self._dropped = True

    @property
    def began_ns(self) -> int:
        """The clock reading at which the open stage began."""
        return self._t0

    @property
    def began_cpu_ns(self) -> int:
        return self._c0

    def __enter__(self):
        self._token = _open_stage.set(self)
        self._ann = _annotate(self.span.op, self.name, self.span.trace_id)
        self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def _lap(self, part: "str | None", now_ns: int, cpu_ns: int) -> None:
        """Close the part that is open, at these clock readings, and
        open `part` (None: none) at the same ones: the parts of one
        entry lie end to end."""
        if self._part is not None:
            if self._part_ann is not None:
                self._part_ann.__exit__(None, None, None)
            self.span._record(
                self._part, self._part_t0, now_ns, cpu_ns - self._part_c0,
                self.chip,
            )
        self._part = part
        if part is not None:
            self._part_t0 = now_ns
            self._part_c0 = cpu_ns
            self._part_ann = _annotate(self.span.op, part, self.span.trace_id)

    def _close(self, t1: int, cpu1: int) -> None:
        """End the stage (and the part open under it) at these clock
        readings."""
        if self._part is not None:
            self._lap(None, t1, cpu1)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if not self._dropped:
            self.span._record(
                self.name, self._t0, t1, cpu1 - self._c0, self.chip,
                self.seconds,
            )

    def _turn(self, name: str, now_ns: int, cpu_ns: int) -> None:
        """End the stage at these clock readings and begin `name` at the
        same ones, on the same span: the stages of one entry lie end to
        end."""
        self._close(now_ns, cpu_ns)
        self.name = name
        self.seconds = None
        self._t0 = now_ns
        self._c0 = cpu_ns
        self._ann = _annotate(self.span.op, name, self.span.trace_id)

    def __exit__(self, *exc):
        self.ended_ns = time.perf_counter_ns()
        self._close(self.ended_ns, time.thread_time_ns())
        _open_stage.reset(self._token)
        return False


class _Activation:
    """Sets the ambient span contextvar for the with-block (children
    started inside pick it up as their parent; grpc_metadata() reads
    it for outgoing hops)."""

    __slots__ = ("span", "_token")

    def __init__(self, span: "Span"):
        self.span = span

    def __enter__(self):
        self._token = _current.set(self.span)
        return self.span

    def __exit__(self, *exc):
        _current.reset(self._token)
        return False


class Span:
    """One timed node of a trace. Thread-safe for stage/event/child
    recording (pipeline stages run in reader/writer threads
    concurrently); start/finish happen in the owning thread."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "op", "name", "server",
        "request_id", "start_ts", "start_ns", "end_ns", "duration_s",
        "cpu_s", "thread", "attrs", "stages", "intervals",
        "stages_dropped", "events", "children", "_lock", "_local_root",
        "_finished", "_c0", "_ident", "_ann",
    )

    def __init__(
        self,
        op: str,
        name: str = "",
        trace_id: str = "",
        parent_id: str = "",
        server: str = "",
        attrs: dict | None = None,
        local_root: bool = True,
    ):
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.span_id = uuid.uuid4().hex[:16]
        self.parent_id = parent_id
        self.op = op
        self.name = name or op
        self.server = server
        self.request_id = _rid.get()
        self.start_ts = time.time()
        # the span's own clock: every interval below is on it, and
        # start_ts says where it lies in wall time
        self.start_ns = time.perf_counter_ns()
        self.end_ns = 0
        self.duration_s = 0.0
        # CPU seconds of the owning thread between start and finish
        # (None until then, and where another thread finished the span)
        self.cpu_s: float | None = None
        self.thread = threading.current_thread().name
        self._ident = threading.get_ident()
        self._c0 = time.thread_time_ns()
        self.attrs = dict(attrs) if attrs else {}
        # stage -> [total_seconds, count, chip, cpu_ns] (chip: last
        # writer wins — one stream runs on one chip; a mesh stream
        # reports "")
        self.stages: dict[str, list] = {}
        # (stage, start_ns, end_ns, thread, cpu_ns); cpu_ns -1 = not read
        self.intervals: list[tuple] = []
        self.stages_dropped = 0
        self.events: list[dict] = []
        self.children: list["Span"] = []
        self._lock = threading.Lock()
        self._local_root = local_root
        self._finished = False
        self._ann = _annotate(op, "", self.trace_id)

    # -------------------------------------------------------- recording

    def child(self, op: str, name: str = "", **attrs) -> "Span":
        c = Span(
            op,
            name=name,
            trace_id=self.trace_id,
            parent_id=self.span_id,
            server=self.server,
            attrs=attrs,
            local_root=False,
        )
        with self._lock:
            # a child begun after its parent finished (a fetch thread that
            # got going late) is timed and kept out of the closed tree
            if not self._finished:
                self.children.append(c)
        return c

    def backdate(self, start_ns: int, cpu_ns: int) -> None:
        """Set the start back to clock readings the owning thread took
        before the span could be made (an HTTP root is made once the
        trace headers are parsed; its request began before that)."""
        self.start_ts -= (self.start_ns - start_ns) / 1e9
        self.start_ns = start_ns
        self._c0 = cpu_ns

    def _record(
        self, stage: str, t0_ns: int, t1_ns: int, cpu_ns: int,
        chip: str = "", seconds: float | None = None,
    ) -> None:
        if seconds is None:
            seconds = (t1_ns - t0_ns) / 1e9
        if seconds < 0.0:
            seconds = 0.0
        thread = threading.current_thread().name
        with self._lock:
            if self._finished:
                # closed under its thread (finish() by the parent of a
                # fetch that nobody waits for): the span keeps the length
                # it was given, and no stage may end after it
                return
            acc = self.stages.get(stage)
            if acc is None:
                self.stages[stage] = [seconds, 1, chip, max(cpu_ns, 0)]
            else:
                acc[0] += seconds
                acc[1] += 1
                if chip:
                    acc[2] = chip
                acc[3] += max(cpu_ns, 0)
            if len(self.intervals) < MAX_INTERVALS:
                self.intervals.append((stage, t0_ns, t1_ns, thread, cpu_ns))
            else:
                self.stages_dropped += 1
        if stage in SUB_STAGES:
            return  # inside its parent: the histogram's sum must not double
        _stage_seconds.observe(seconds, op=self.op, stage=stage, chip=chip)
        if stage not in DEVICE_STAGES:
            return  # placement reads these two, and nobody any other
        with _ewma_lock:
            key = (self.op, stage)
            prev = _stage_ewma.get(key)
            _stage_ewma[key] = (
                seconds
                if prev is None
                else prev + EWMA_ALPHA * (seconds - prev)
            )

    def add_stage(self, stage: str, seconds: float, chip: str = "") -> None:
        """A stage timed after the fact: it ended now."""
        now = time.perf_counter_ns()
        self._record(
            stage, now - int(max(seconds, 0.0) * 1e9), now, -1, chip, seconds
        )

    def add_interval(
        self, stage: str, t0_ns: int, t1_ns: int, cpu_ns: int = -1
    ) -> None:
        """A stage whose two ends were read (``time.perf_counter_ns``)
        where no timer could be open: before the span was made."""
        self._record(stage, t0_ns, t1_ns, cpu_ns)

    def stage(self, name: str, chip: str = "") -> _StageTimer:
        return _StageTimer(self, name, chip)

    def event(self, name: str, **attrs) -> None:
        with self._lock:
            self.events.append(
                {"ts": time.time(), "name": name, "attrs": attrs}
            )

    def count(self, name: str, n: int) -> None:
        with self._lock:
            self.attrs[name] = self.attrs.get(name, 0) + n

    # --------------------------------------------------------- lifecycle

    def finish(self, end_ns: int = 0, **attrs) -> bool:
        """`end_ns`: the span ended at that clock reading, before it
        could be closed (backdate()'s twin: the wait probes close an
        interval after they have summed it up). `attrs` are the closing
        call's last word on the span. -> whether THIS call closed it: a
        span that two threads may close (a fetch that outlives the
        reconstruction it was started for) takes the first one's end
        and attributes, whole."""
        with self._lock:
            if self._finished:
                return False
            self._finished = True
            if attrs:
                self.attrs.update(attrs)
            self.end_ns = end_ns or time.perf_counter_ns()
            self.duration_s = (self.end_ns - self.start_ns) / 1e9
            if threading.get_ident() == self._ident:
                self.cpu_s = (time.thread_time_ns() - self._c0) / 1e9
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if self._local_root:
            _complete_root(self)
        return True

    # ------------------------------------------------------------ export

    def to_dict(self) -> dict:
        with self._lock:
            end_ns = self.end_ns if self._finished else time.perf_counter_ns()
            return {
                "trace_id": self.trace_id,
                "span_id": self.span_id,
                "parent_span_id": self.parent_id,
                "op": self.op,
                "name": self.name,
                "server": self.server,
                "request_id": self.request_id,
                "start_ts": self.start_ts,
                "start_ns": self.start_ns,
                "end_ns": end_ns,
                "duration_s": (end_ns - self.start_ns) / 1e9,
                "cpu_s": self.cpu_s,
                "thread": self.thread,
                "attrs": dict(self.attrs),
                "stages": {
                    s: {
                        "seconds": a[0], "count": a[1], "chip": a[2],
                        "cpu_s": a[3] / 1e9,
                    }
                    for s, a in self.stages.items()
                },
                "intervals": [list(iv) for iv in self.intervals],
                "stages_dropped": self.stages_dropped,
                "events": [dict(e) for e in self.events],
                "children": [c.to_dict() for c in self.children],
            }


# --------------------------------------------------------------------------
# Root completion: ring + derived metrics + slow-op log.
# --------------------------------------------------------------------------


def _tree_stage_totals(doc: dict) -> dict[str, float]:
    totals: dict[str, float] = {}
    stack = [doc]
    while stack:
        d = stack.pop()
        for s, a in d["stages"].items():
            if s not in SUB_STAGES:  # already inside its parent's seconds
                totals[s] = totals.get(s, 0.0) + a["seconds"]
        stack.extend(d["children"])
    return totals


def overlap_efficiency(doc: dict) -> float | None:
    """Device time hidden behind I/O / total device time for one root
    span dict (None when the op did no device work). See the module
    docstring for the derivation.

    Two estimators of exposed device time, combined by max:

    - wall residue ``wall - host``: host stages run in parallel
      threads (reader disk_read vs sink write_sink vs both sides'
      queue_wait), so their SUM can exceed wall and the residue alone
      would then read 0 ("fully hidden") no matter what the device did;
    - ``device_drain``: a DIRECT measurement — every second blocked in
      ``to_host`` is a second the device was not hidden.
    """
    totals = _tree_stage_totals(doc)
    device = sum(v for s, v in totals.items() if s in DEVICE_STAGES)
    if device <= 0.0:
        return None
    host = sum(v for s, v in totals.items() if s not in DEVICE_STAGES)
    residue = max(doc["duration_s"] - host, 0.0)
    exposed = min(max(residue, totals.get("device_drain", 0.0)), device)
    return (device - exposed) / device


def _doc_span_count(doc: dict) -> int:
    n = 0
    stack = [doc]
    while stack:
        d = stack.pop()
        n += 1
        stack.extend(d["children"])
    return n


def _complete_root(span: Span) -> None:
    global _ring_spans
    doc = span.to_dict()
    if span.op == PROBE_OP:
        doc["span_count"] = 1
        with _lock:
            _probe_ring.append(doc)
        return
    eff = overlap_efficiency(doc)
    if eff is not None:
        doc["overlap_efficiency"] = round(eff, 4)
    # Gauge per op CLASS over each EC subtree, not just the local root:
    # behind an RPC adoption the root op is rpc.*, but the tuning
    # question — "is encode/rebuild staging actually overlapping on
    # this host?" — is asked per ec.* op.
    stack = [doc]
    while stack:
        d = stack.pop()
        if d is doc or d["op"].startswith("ec."):
            e = overlap_efficiency(d)
            if e is not None:
                _overlap_eff.set(e, op=d["op"])
        stack.extend(d["children"])
    doc["span_count"] = _doc_span_count(doc)
    with _lock:
        # manual maxlen handling so the span-count budget stays exact:
        # deque's own eviction on append would bypass the accounting
        while len(_ring) >= (_ring.maxlen or DEFAULT_RING):
            _ring_spans -= _ring.popleft().get("span_count", 1)
        _ring.append(doc)
        _ring_spans += doc["span_count"]
        # byte-bound analog: a span-heavy op class evicts oldest docs
        # beyond the trace-count bound too (always keep the newest)
        while _ring_spans > _max_ring_spans and len(_ring) > 1:
            _ring_spans -= _ring.popleft().get("span_count", 1)
        slow = _slow_op_s
    if 0.0 < slow <= doc["duration_s"]:
        _log.warning(
            "slow op %s (%.3fs > %.3fs) request_id=%s trace=%s\n%s",
            span.op, doc["duration_s"], slow,
            doc["request_id"] or "-", span.trace_id, format_tree(doc),
        )


def format_tree(doc: dict, indent: int = 0) -> str:
    """Human-readable span tree with per-stage durations (the slow-op
    log body). The root line carries the request id and root op so a
    logged tree can be joined against gateway access logs even when the
    surrounding log prefix is stripped."""
    pad = "  " * indent
    stages = " ".join(
        f"{s}={a['seconds'] * 1000:.1f}ms/{a['count']}"
        for s, a in sorted(doc["stages"].items())
    )
    line = (
        f"{pad}{doc['op']}"
        f"{' [' + doc['name'] + ']' if doc['name'] != doc['op'] else ''}"
        f" {doc['duration_s'] * 1000:.1f}ms"
    )
    if doc.get("cpu_s") is not None:
        line += f" cpu={doc['cpu_s'] * 1000:.1f}ms"
    if indent == 0:
        line += (
            f" root={doc['op']}"
            f" rid={doc.get('request_id') or '-'}"
            f" trace={doc.get('trace_id', '')}"
        )
    if doc.get("server"):
        line += f" @{doc['server']}"
    if stages:
        line += f" | {stages}"
    out = [line]
    for ev in doc["events"]:
        out.append(f"{pad}  * {ev['name']} {ev['attrs']}")
    for c in doc["children"]:
        out.append(format_tree(c, indent + 1))
    return "\n".join(out)


# --------------------------------------------------------------------------
# Module API (production call sites).
# --------------------------------------------------------------------------


def configure(
    enabled: bool | None = None,
    ring_size: int | None = None,
    slow_op_s: float | None = None,
    ring_spans: int | None = None,
) -> dict:
    """Arm/disarm the tracer and tune the ring / slow-op threshold.
    ``slow_op_s`` <= 0 disables the slow-op log. ``ring_spans`` bounds
    the TOTAL span count retained across the ring (memory bound for
    span-heavy op classes). Returns the effective config."""
    global armed, _ring, _probe_ring, _ring_spans, _max_ring_spans, _slow_op_s
    with _lock:
        if ring_size is not None and ring_size > 0:
            if _ring.maxlen != ring_size:
                _ring = deque(_ring, maxlen=int(ring_size))
                _probe_ring = deque(_probe_ring, maxlen=int(ring_size))
                _ring_spans = sum(
                    d.get("span_count", 1) for d in _ring
                )
        if ring_spans is not None and ring_spans > 0:
            _max_ring_spans = int(ring_spans)
            while _ring_spans > _max_ring_spans and len(_ring) > 1:
                _ring_spans -= _ring.popleft().get("span_count", 1)
        if slow_op_s is not None:
            _slow_op_s = max(float(slow_op_s), 0.0)
        if enabled is not None:
            armed = bool(enabled)
        config = {
            "enabled": armed,
            "ring_size": _ring.maxlen,
            "ring_spans": _max_ring_spans,
            "slow_op_s": _slow_op_s,
        }
    if enabled is not None:
        # the wait probes live exactly as long as the tracer is armed;
        # outside the lock: a probe closing a span takes it
        from . import interp_probe

        if enabled:
            interp_probe.start()
        else:
            interp_probe.stop()
    return config


def reset() -> None:
    """Drop recorded traces (tests)."""
    global _ring_spans
    with _lock:
        _ring.clear()
        _probe_ring.clear()
        _ring_spans = 0
    with _ewma_lock:
        _stage_ewma.clear()


def stage_ewmas() -> dict[str, float]:
    """Per-``op/stage`` EWMA of the seconds of ``h2d_dispatch`` and
    ``device_drain`` entries (armed runs only) — the heartbeat
    telemetry payload, all of which ec/placement.py reads."""
    with _ewma_lock:
        return {f"{op}/{st}": v for (op, st), v in _stage_ewma.items()}


def start(op: str, name: str = "", parent: "Span | None" = None, **attrs):
    """Open a span (None when disarmed — every downstream helper
    accepts None). With no explicit ``parent`` the ambient span (set by
    :func:`activate`) is the parent; no ambient span = a new local
    root."""
    if not armed:
        return None
    p = parent if parent is not None else _current.get()
    if p is not None:
        return p.child(op, name, **attrs)
    return Span(op, name=name, attrs=attrs)


def start_from_metadata(
    op: str, md: dict, name: str = "", server: str = "", **attrs
):
    """Server-side span adoption: continue the trace carried in gRPC
    metadata (a LOCAL root here — its parent lives on the caller).
    None when disarmed."""
    if not armed:
        return None
    return Span(
        op,
        name=name,
        trace_id=md.get(TRACE_ID_KEY, ""),
        parent_id=md.get(PARENT_SPAN_KEY, ""),
        server=server,
        attrs=attrs,
    )


def start_from_headers(op: str, headers, name: str = "", server: str = "",
                       **attrs):
    """HTTP-side span adoption: continue the trace carried in request
    headers (a LOCAL root here — its parent span lives on the calling
    server/client). ``headers`` is any case-insensitive mapping with
    ``.get`` (http.client/BaseHTTPRequestHandler message objects
    qualify). None when disarmed."""
    if not armed:
        return None
    return Span(
        op,
        name=name,
        trace_id=headers.get(TRACE_ID_HEADER) or "",
        parent_id=headers.get(PARENT_SPAN_HEADER) or "",
        server=server,
        attrs=attrs,
    )


def http_headers(span=None, headers: dict | None = None) -> dict | None:
    """Outgoing HTTP headers carrying the trace context of ``span`` (or
    the ambient span). Returns ``headers`` with the two trace headers
    merged in, or None when there is nothing to carry (the request id
    rides separately via request_id.inject)."""
    sp = span
    if sp is None and armed:
        sp = _current.get()
    if sp is None:
        return headers
    h = headers if headers is not None else {}
    h[TRACE_ID_HEADER] = sp.trace_id
    h[PARENT_SPAN_HEADER] = sp.span_id
    return h


def set_current(span):
    """Install ``span`` as the ambient span; returns a token for
    :func:`reset_current` (the non-with-block form of :func:`activate`,
    for request handlers whose enter/exit live in different methods).
    None-safe: returns None when ``span`` is None."""
    if span is None:
        return None
    return _current.set(span)


def reset_current(token) -> None:
    if token is not None:
        _current.reset(token)


def current():
    """The ambient span, or None (always None when disarmed — the
    contextvar is not even read)."""
    if not armed:
        return None
    return _current.get()


def activate(span):
    """Context manager setting the ambient span for the with-block;
    no-op singleton when ``span`` is None."""
    if span is None:
        return _NOOP
    return _Activation(span)


def finish(span) -> None:
    if span is not None:
        span.finish()


def stage(span, name: str, chip: str = ""):
    """Per-batch stage timer: ``with trace.stage(sp, "disk_read"): …``.
    One is-None check and the singleton no-op when disarmed."""
    if span is None:
        return _NOOP
    return _StageTimer(span, name, chip)


def lap(part: str) -> None:
    """Begin sub-stage ``<stage>.<part>`` of the stage the calling
    thread has open, ending the part before it: ``trace.lap("put")``
    inside the backend splits the pipeline's ``h2d_dispatch`` without
    the backend being handed a span. Parts lie end to end (the last
    ends with its stage), so they add up to their parent but for what
    ran before the first. Nothing when disarmed (one module-bool
    check), when no stage is open, and where the open stage has no
    such part (:data:`SUB_STAGES` is the whole list)."""
    if not armed:
        return
    parent = _open_stage.get()
    if parent is None:
        return
    name = _SUB_NAME.get((parent.name, part))
    if name is not None:
        parent._lap(name, time.perf_counter_ns(), time.thread_time_ns())


def turn(name: str) -> None:
    """The stage that the calling thread has open ends here, and stage
    `name` begins on the same span at the same clock readings: the
    steps of one read from a peer (:data:`TURN_STAGES`) lie end to end
    on its ``ec.peer_read`` span though the code of each step is handed
    no span. Nothing when disarmed (one module-bool check), where no
    such stage is open (the same client calls under a rebuild's
    ``peer_fetch``), where `name` is open already, and for a `name`
    that is no such stage."""
    if not armed:
        return
    timer = _open_stage.get()
    if (
        timer is None or timer.name == name
        or timer.name not in TURN_STAGES or name not in TURN_STAGES
    ):
        return
    timer._turn(name, time.perf_counter_ns(), time.thread_time_ns())


def count(name: str, n: int) -> None:
    """Add `n` to the seam counter `name` (``h2d_bytes``, ``d2h_bytes``,
    ``d2h_dense_bytes``, ``read_bytes``, ``read_reused_bytes``,
    ``batches``): an attribute of the span whose stage the calling
    thread has open, and ``sw_ec_*_total{op}`` on /metrics. Nothing
    when disarmed (one module-bool check) or with no stage open."""
    if not armed:
        return
    parent = _open_stage.get()
    if parent is None:
        return
    parent.span.count(name, n)
    _seam_counters[name].inc(n, op=parent.span.op)


def book_return(stamp_ns: int) -> None:
    """A native call of utils/native.py has come back to the interpreter
    (armed only: the caller checks). `stamp_ns` is what the C side read
    off ``CLOCK_MONOTONIC`` as its last act; now less that is how long
    this thread, ready to run, waited to hold the interpreter again. It
    goes to the span whose stage the thread has open, else to the
    ambient span, as ``interp_wait_ns`` beside ``interp_returns``."""
    now = time.perf_counter_ns()
    timer = _open_stage.get()
    span = timer.span if timer is not None else _current.get()
    if span is None or stamp_ns <= 0:
        return
    with span._lock:
        if span._finished:
            return  # closed under its thread: see Span._record
        attrs = span.attrs
        attrs["interp_wait_ns"] = (
            attrs.get("interp_wait_ns", 0) + max(now - stamp_ns, 0)
        )
        attrs["interp_returns"] = attrs.get("interp_returns", 0) + 1


def add_stage(span, name: str, seconds: float, chip: str = "") -> None:
    if span is not None:
        span.add_stage(name, seconds, chip)


def event(span, name: str, **attrs) -> None:
    if span is not None:
        span.event(name, **attrs)


def grpc_metadata(span=None, extra=None):
    """Outgoing gRPC metadata carrying the active request id and (when
    armed and a span is active) the trace context. Returns None when
    there is nothing to carry — ``grpc`` accepts ``metadata=None``.
    ``extra`` is an iterable of additional (key, value) pairs."""
    md = list(extra) if extra else []
    rid = _rid.get()
    if rid:
        md.append((REQUEST_ID_KEY, rid))
    sp = span
    if sp is None and armed:
        sp = _current.get()
    if sp is not None:
        md.append((TRACE_ID_KEY, sp.trace_id))
        md.append((PARENT_SPAN_KEY, sp.span_id))
    return tuple(md) if md else None


def metadata_dict(context) -> dict:
    """Lower-cased invocation metadata of a gRPC servicer context
    (empty for in-process calls passing context=None)."""
    md: dict = {}
    if context is None:
        return md
    try:
        for k, v in context.invocation_metadata():
            md[k.lower()] = v
    except Exception:
        pass
    return md


# --------------------------------------------------------------------------
# Ring export.
# --------------------------------------------------------------------------


def traces(
    trace_id: str = "", op: str = "", min_ms: float = 0.0
) -> list[dict]:
    """Completed root spans, oldest first (the wait probes' interval
    spans, which have a ring of their own, before all others). Filters:
    one trace id (a cross-server trace is several roots sharing it), a
    root ``op`` class, and/or a minimum root duration in milliseconds —
    the ``/debug/traces?op=&min_ms=`` query surface."""
    with _lock:
        docs = list(_probe_ring) + list(_ring)
    if trace_id:
        docs = [d for d in docs if d["trace_id"] == trace_id]
    if op:
        docs = [d for d in docs if d["op"] == op]
    if min_ms > 0.0:
        docs = [d for d in docs if d["duration_s"] * 1000.0 >= min_ms]
    return docs


def chrome_trace(trace_id: str = "", docs: list[dict] | None = None) -> dict:
    """Chrome ``trace_event`` JSON (the dict; ``json.dump`` it) for the
    recorded traces — loadable in Perfetto / chrome://tracing. Each
    server becomes a process, each thread that worked for a root span a
    row of it: spans lie on their owning thread's row and every stage
    interval is an ``X`` event on the row of the thread that ran it, so
    a pipeline's reader, dispatcher and sink show side by side. Stage
    totals and attrs ride in the span events' ``args``."""
    if docs is None:
        docs = traces(trace_id)
    events: list[dict] = []
    pids: dict[str, int] = {}
    tid_next: dict[int, int] = {}

    def emit(doc: dict, pid: int, row) -> None:
        args = {
            "trace_id": doc["trace_id"],
            "span_id": doc["span_id"],
            "request_id": doc["request_id"],
            "stages_ms": {
                s: round(a["seconds"] * 1000.0, 3)
                for s, a in doc["stages"].items()
            },
        }
        if doc.get("cpu_s") is not None:
            args["cpu_ms"] = round(doc["cpu_s"] * 1000.0, 3)
        if doc.get("stages_dropped"):
            args["stages_dropped"] = doc["stages_dropped"]
        if doc.get("overlap_efficiency") is not None:
            args["overlap_efficiency"] = doc["overlap_efficiency"]
        args.update(doc["attrs"])
        tid = row(doc.get("thread", ""))
        events.append(
            {
                "name": doc["name"],
                "cat": doc["op"],
                "ph": "X",
                "ts": doc["start_ts"] * 1e6,
                "dur": max(doc["duration_s"], 1e-6) * 1e6,
                "pid": pid,
                "tid": tid,
                "args": args,
            }
        )
        for stage, t0, t1, thread, cpu_ns in doc.get("intervals", ()):
            ev = {
                "name": stage,
                "cat": doc["op"],
                "ph": "X",
                # the span's monotonic clock, laid where start_ts says
                "ts": doc["start_ts"] * 1e6 + (t0 - doc["start_ns"]) / 1e3,
                "dur": max(t1 - t0, 1) / 1e3,
                "pid": pid,
                "tid": row(thread),
                "args": {"trace_id": doc["trace_id"], "span_id": doc["span_id"]},
            }
            if cpu_ns >= 0:
                ev["args"]["cpu_ms"] = round(cpu_ns / 1e6, 3)
            events.append(ev)
        for ev in doc["events"]:
            events.append(
                {
                    "name": ev["name"],
                    "cat": doc["op"],
                    "ph": "i",
                    "s": "t",
                    "ts": ev["ts"] * 1e6,
                    "pid": pid,
                    "tid": tid,
                    "args": dict(ev["attrs"]),
                }
            )
        for c in doc["children"]:
            emit(c, pid, row)

    for doc in docs:
        server = doc.get("server") or "proc"
        pid = pids.get(server)
        if pid is None:
            pid = pids[server] = len(pids) + 1
            events.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": 0,
                    "args": {"name": server},
                }
            )
        rows: dict[str, int] = {}

        def row(thread: str, doc=doc, pid=pid, rows=rows) -> int:
            tid = rows.get(thread)
            if tid is None:
                tid = rows[thread] = tid_next[pid] = tid_next.get(pid, 0) + 1
                label = f"{doc['op']} {doc['trace_id'][:8]}"
                events.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": pid,
                        "tid": tid,
                        "args": {
                            "name": f"{label} {thread}" if thread else label
                        },
                    }
                )
            return tid

        emit(doc, pid, row)
    return {"traceEvents": events, "displayTimeUnit": "ms"}
