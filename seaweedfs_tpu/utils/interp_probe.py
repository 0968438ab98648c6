"""Wait probes: how long a thread that is ready to run waits for the
interpreter, how long for a core, and which class of thread burnt the
process's CPU.

The tracer's spans say how long a stage took and how much CPU its thread
read; wall less CPU is "did not run", and that is a read, a lock, a core
or the interpreter. Two probes tell the last two apart, while the tracer
is armed (``trace.configure`` starts and stops them; disarmed there is no
thread and nothing is allocated):

- the **Python probe**, a daemon thread (:data:`THREAD_NAME`) that sleeps
  :data:`PERIOD_NS` in a loop and records how late it woke
  (``perf_counter_ns`` at wake less the deadline): ready to run until it
  HELD THE INTERPRETER;
- its **native twin**, a pthread inside ``libseaweed_native.so`` that
  never touches Python and does the same with ``clock_nanosleep``: ready
  to run until it HAD A CORE. Where the library is missing there is no
  twin, and ``core_wait_ns`` is left out.

The first less the second is the queue for the interpreter itself. Every
:data:`INTERVAL_NS` the Python probe closes a root span ``interp.probe``
over the interval, with attributes

- ``py_wait_ns``, ``core_wait_ns``: ``{count, sum, p50, p95, max}`` of the
  interval's waits, and ``py_samples``, ``core_samples``: every sample as
  ``[wake_ns, wait_ns]`` on the spans' clock (``perf_counter_ns`` is
  ``CLOCK_MONOTONIC``), so that a reader can cut them by time;
- ``cpu_ns``: the growth over the interval of the CPU clock of every live
  Python thread, summed by class (:func:`thread_class`, from the thread's
  name as the program gives it), and ``native``: the process's CPU less
  their sum (the runtime's transfer threads, the native readers and
  writers; a thread that ended inside the interval leaves its last slice
  there); ``process_cpu_ns`` is the whole.

``/metrics`` carries the same as ``sw_interp_wait_seconds``,
``sw_core_wait_seconds`` (histograms) and
``sw_thread_cpu_seconds_total{cls}``. The seam stamps of
``utils/native.py`` (``interp_wait_ns``, ``interp_returns`` on a span)
are the same measurement on the real worker threads at the real moments.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time

from . import metrics as _M
from . import trace as _trace

# A probe's sleep. Every wake-up of the Python probe is a hand-off of the
# interpreter to it and one back: 5 ms keeps them under 200 a second alone,
# some 125 beside a loaded server. On the benchmark's host the probes cost a
# traced GET cell 2-3 % of its GETs, and sleeps of 2 to 20 ms read alike
# inside the noise (PERF.md section 5, PR 34).
PERIOD_NS = 5_000_000
INTERVAL_NS = 100_000_000   # one `interp.probe` span
THREAD_NAME = "sw-interp-probe"
SPAN_OP = _trace.PROBE_OP  # "interp.probe": trace.py keeps them a ring of their own

_WAIT_BUCKETS = (
    0.00005, 0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
    0.05, 0.1, 0.25, 1.0,
)
_interp_wait = _M.REGISTRY.histogram(
    "sw_interp_wait_seconds",
    "how late a Python thread that slept 5 ms held the interpreter again "
    "(tracer armed only)",
    (), _WAIT_BUCKETS,
)
_core_wait = _M.REGISTRY.histogram(
    "sw_core_wait_seconds",
    "how late a native thread that slept 5 ms ran again: the wait for a "
    "core, no interpreter in it (tracer armed only)",
    (), _WAIT_BUCKETS,
)
_thread_cpu = _M.REGISTRY.counter(
    "sw_thread_cpu_seconds_total",
    "CPU seconds by class of thread (from the thread's name; native = the "
    "process's CPU no Python thread read; tracer armed only)",
    ("cls",),
)

# A thread's class, by the name the program gives it; the first prefix
# that fits. What fits none is the program's if its target lives in this
# package, else `other_python`: a thread the program did not start (in
# the benchmark's cells the load generator).
_CLASS_BY_PREFIX = (
    ("http-pool-", "http_workers"),
    ("http-accept-", "http_accept"),
    ("ec-pipe-reader", "pipe_reader"),
    ("ec-pipe-sink", "pipe_sink"),
    # the servers' RPC pools; a rebuild's dispatcher is one of them
    ("grpc-", "rpc"),
    # what carries a peer's shard range: the holder's connection threads
    # of its native shard plane (ec/net_plane.py), the reader's fetches
    # of a reconstruction's rows (ec/ec_volume.py)
    ("shard-net-conn-", "shard_plane"),
    ("ec-peer-fetch", "peer_fetch"),
    (THREAD_NAME, "probe"),
)
_PACKAGE = __name__.split(".")[0]


def thread_class(thread: threading.Thread) -> str:
    name = thread.name
    for prefix, cls in _CLASS_BY_PREFIX:
        if name.startswith(prefix):
            return cls
    origin = getattr(thread, "_target", None) or type(thread)
    module = getattr(origin, "__module__", None) or ""
    if module.split(".")[0] == _PACKAGE:
        return "program_other"
    return "other_python"


def summary(waits: list[int]) -> dict:
    """``{count, sum, p50, p95, max}`` of waits in ns (nearest rank)."""
    if not waits:
        return {"count": 0, "sum": 0, "p50": 0, "p95": 0, "max": 0}
    s = sorted(waits)
    n = len(s)
    return {
        "count": n, "sum": sum(s), "p50": s[n // 2],
        "p95": s[min(int(0.95 * n), n - 1)], "max": s[-1],
    }


# --------------------------------------------------------- thread CPU


def _cpu_clock_id(tid: int) -> int:
    """The CPU-time clock of kernel thread `tid`: what
    ``pthread_getcpuclockid`` computes, from the id and not from a
    ``pthread_t`` that dangles once its thread has ended
    (``CPUCLOCK_SCHED | CPUCLOCK_PERTHREAD_MASK`` over ``~tid << 3``)."""
    return ((~tid) << 3) | 6


def _clock_cpu_ns(tid: int) -> int | None:
    try:
        return time.clock_gettime_ns(_cpu_clock_id(tid))
    except (OSError, OverflowError):
        return None


_TICK_NS = 1_000_000_000 // (os.sysconf("SC_CLK_TCK") or 100)


def _proc_cpu_ns(tid: int) -> int | None:
    """utime + stime of ``/proc/self/task/<tid>/stat``, where the
    sandbox refuses the thread's clock: whole ticks."""
    try:
        with open(f"/proc/self/task/{tid}/stat", "rb") as f:
            fields = f.read().rsplit(b") ", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) * _TICK_NS
    except (OSError, IndexError, ValueError):
        return None


def _pick_cpu_reader():
    if _clock_cpu_ns(threading.get_native_id()) is not None:
        return _clock_cpu_ns
    return _proc_cpu_ns


# ------------------------------------------------------- the native twin


class _NativeTwin:
    """The pthread probe of libseaweed_native.so, and a cursor into its
    ring. None of it where the library cannot be loaded."""

    def __init__(self):
        from . import native  # ImportError: no twin

        self._lib = native._lib
        rc = self._lib.sn_probe_start(PERIOD_NS)
        if rc != 0:
            raise OSError(-rc, f"sn_probe_start: {os.strerror(-rc)}")
        self._cursor = ctypes.c_uint64(self._lib.sn_probe_head())
        self._buf = (ctypes.c_int64 * (2 * 512))()

    def drain(self) -> list[list[int]]:
        n = self._lib.sn_probe_read(
            ctypes.byref(self._cursor), ctypes.addressof(self._buf), 512
        )
        flat = self._buf[: 2 * n]
        return [[flat[i], flat[i + 1]] for i in range(0, 2 * n, 2)]

    def stop(self) -> None:
        self._lib.sn_probe_stop()


# ------------------------------------------------------ the Python probe


class _Probe:
    def __init__(self):
        self._stop = False
        try:
            self._twin = _NativeTwin()
        except (ImportError, OSError, AttributeError):
            self._twin = None
        self._read_cpu = _pick_cpu_reader()
        self._cpu_last: dict[int, tuple[int, str]] = {}  # tid -> (CPU ns, class)
        self._native_debt = 0
        self._thread = threading.Thread(
            target=self._run, name=THREAD_NAME, daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop = True
        self._thread.join(timeout=5.0)
        if self._twin is not None:
            self._twin.stop()

    def _run(self) -> None:
        period_s = PERIOD_NS / 1e9
        self._thread_cpu_growth()  # the baseline: history is nobody's
        samples: list[list[int]] = []
        t0 = time.perf_counter_ns()
        c0 = time.thread_time_ns()
        p0 = time.process_time_ns()
        while not self._stop:
            deadline = time.perf_counter_ns() + PERIOD_NS
            time.sleep(period_s)
            now = time.perf_counter_ns()
            samples.append([now, max(now - deadline, 0)])
            if now - t0 >= INTERVAL_NS:
                p1 = time.process_time_ns()
                self._close(t0, now, c0, p1 - p0, samples)
                samples = []
                t0, c0, p0 = now, time.thread_time_ns(), p1

    def _thread_cpu_growth(self) -> dict[str, int]:
        """CPU ns every live Python thread read since the last call, by
        class. A thread first seen has all of its CPU in this interval;
        a kernel id that another thread has taken over starts again."""
        grown: dict[str, int] = {}
        last, seen = self._cpu_last, {}
        for t in threading.enumerate():
            tid = t.native_id
            cpu = self._read_cpu(tid) if tid is not None else None
            if cpu is None:
                continue
            before, cls = last.get(tid) or (0, None)
            if cls is None or cpu < before:  # a new thread under this id
                before, cls = 0, thread_class(t)
            grown[cls] = grown.get(cls, 0) + cpu - before
            seen[tid] = (cpu, cls)
        self._cpu_last = seen
        return grown

    def _close(
        self, t0: int, t1: int, c0: int, process_cpu_ns: int, py: list
    ) -> None:
        cpu = self._thread_cpu_growth()
        # the CPU clocks tick (10 ms under some sandboxes): an interval in
        # which the threads' ticks outrun the process's owes the next one
        native = process_cpu_ns - sum(cpu.values()) + self._native_debt
        self._native_debt = min(native, 0)
        cpu["native"] = max(native, 0)
        attrs = {
            "period_ns": PERIOD_NS,
            "cpu_ns": cpu,
            "process_cpu_ns": process_cpu_ns,
        }
        probes = [("py", py, _interp_wait)]
        if self._twin is not None:
            probes.append(("core", self._twin.drain(), _core_wait))
        for which, got, histogram in probes:
            waits = [w for _t, w in got]
            attrs[f"{which}_wait_ns"] = summary(waits)
            attrs[f"{which}_samples"] = got
            histogram.observe_many(w / 1e9 for w in waits)
        for cls, ns in cpu.items():
            if ns > 0:
                _thread_cpu.inc(ns / 1e9, cls=cls)
        if not _trace.armed:
            return  # disarmed while this interval ran: leave nothing behind
        span = _trace.Span(SPAN_OP, attrs=attrs)
        span.backdate(t0, c0)
        span.finish(t1)


_lock = threading.Lock()
_probe: _Probe | None = None
_pid = 0


def start() -> None:
    """Start both probes (nothing where they run already)."""
    global _probe, _pid
    with _lock:
        if _probe is not None and _pid == os.getpid():
            return
        _probe, _pid = _Probe(), os.getpid()


def stop() -> None:
    """Stop and join both probes (nothing where none runs). A forked
    child inherits neither thread: it only forgets them."""
    global _probe
    with _lock:
        probe, _probe = _probe, None
        if probe is not None and _pid == os.getpid():
            probe.stop()


def running() -> bool:
    return _probe is not None and _pid == os.getpid()

