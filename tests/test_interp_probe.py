"""The wait probes (utils/interp_probe.py) and the return stamps of the
native seams (utils/native.py): the armed tracer measures how long a
thread that is ready to run waits for the interpreter, how long for a
core, and which class of thread burnt the CPU; disarmed there is no
thread, no span and no attribute.
"""

from __future__ import annotations

import os
import socket
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.utils import interp_probe, native, trace
from seaweedfs_tpu.utils import metrics as M

MS = 1_000_000


@pytest.fixture
def armed(disarmed):
    trace.configure(enabled=True, ring_size=4096, slow_op_s=0.0)
    trace.reset()
    yield trace


def probe_docs() -> list[dict]:
    return trace.traces(op=interp_probe.SPAN_OP)


def samples(docs, key: str, t0: int = 0, t1: int = 1 << 62) -> list[int]:
    """Waits of the `key` samples whose wake time lies in [t0, t1]."""
    return [w for d in docs for t, w in d["attrs"][key] if t0 <= t <= t1]


def quantile(waits: list[int], q: float) -> int:
    s = sorted(waits)
    return s[min(int(q * len(s)), len(s) - 1)]


def wait_for_spans(n: int, timeout: float = 5.0) -> list[dict]:
    deadline = time.time() + timeout
    while len(probe_docs()) < n and time.time() < deadline:
        time.sleep(0.02)
    return probe_docs()


def python_spin(seconds: float) -> tuple[int, int]:
    """Hold the interpreter in a pure-Python loop; (start, end) on the
    spans' clock."""
    t0 = time.perf_counter_ns()
    end = t0 + int(seconds * 1e9)
    x = 0
    while time.perf_counter_ns() < end:
        x += 1
    return t0, time.perf_counter_ns()


# native work that never comes back to the interpreter inside a call:
# some 20 ms of GF(2^8) products a call
_COEF = np.arange(1, 41, dtype=np.uint8).reshape(4, 10)
_DATA = np.full((10, 4 << 20), 7, dtype=np.uint8)


def native_spin(until: float) -> None:
    while time.perf_counter() < until:
        native.rs_apply(_COEF, _DATA)


# ------------------------------------------------------ the two probes


def test_python_spinner_raises_the_python_probe_and_not_the_native(armed):
    """A thread that holds the interpreter makes the Python probe wait
    a switch interval for it; the native twin never asks for it."""
    time.sleep(0.25)  # both probes alone
    quiet_until = time.perf_counter_ns()
    window = []
    t = threading.Thread(target=lambda: window.append(python_spin(0.08)))
    t.start()
    t.join()
    time.sleep(0.15)  # the interval that holds the spin closes
    docs = wait_for_spans(3)
    t0, t1 = window[0]
    t0 += interp_probe.PERIOD_NS  # the sleep that was under way when the spin began
    py_quiet = samples(docs, "py_samples", t1=quiet_until)
    py_spun = samples(docs, "py_samples", t0, t1)
    core_spun = samples(docs, "core_samples", t0, t1)
    assert py_quiet and py_spun and core_spun
    assert quantile(py_spun, 0.5) >= 2 * MS, (py_spun, py_quiet)
    assert quantile(py_spun, 0.5) > 4 * quantile(py_quiet, 0.5)
    # the native probe went on waking every period, as late as before
    assert len(core_spun) > len(py_spun)
    assert quantile(core_spun, 0.5) < 1 * MS, core_spun


def test_native_spin_on_one_core_raises_both_probes(disarmed):
    """Every core busy (the process pinned to one, eight native
    spinners on it): both probes wake late, the native one too (held to
    the tail alone: other test processes share the machine). A
    sleeper that wakes pre-empts a spinner most of the time, so the
    MEDIAN hardly moves: the tail carries the wait for a core."""
    allowed = os.sched_getaffinity(0)
    os.sched_setaffinity(0, {min(allowed)})  # threads started now inherit it
    try:
        trace.configure(enabled=True, ring_size=4096, slow_op_s=0.0)
        trace.reset()
        time.sleep(0.35)
        quiet_until = time.perf_counter_ns()
        until = time.perf_counter() + 0.6
        spinners = [
            threading.Thread(target=native_spin, args=(until,)) for _ in range(8)
        ]
        for t in spinners:
            t.start()
        for t in spinners:
            t.join()
        busy_until = time.perf_counter_ns()
        time.sleep(0.15)
        docs = wait_for_spans(8)
    finally:
        trace.configure(enabled=False)
        os.sched_setaffinity(0, allowed)
    for key in ("py_samples", "core_samples"):
        quiet = samples(docs, key, t1=quiet_until)
        busy = samples(docs, key, quiet_until, busy_until)
        assert quiet and busy, key
        assert quantile(busy, 0.95) >= MS // 2, (key, sorted(busy)[-20:])
        assert quantile(busy, 0.95) > 2 * quantile(quiet, 0.5), key


def test_disarmed_there_is_no_probe_thread_and_armed_there_is_one(disarmed):
    before = sorted(t.name for t in threading.enumerate())
    assert not interp_probe.running()
    assert interp_probe.THREAD_NAME not in before
    head = native._lib.sn_probe_head()
    time.sleep(0.02)
    assert native._lib.sn_probe_head() == head  # no native thread either
    trace.configure(enabled=True)
    try:
        assert interp_probe.running()
        names = [t.name for t in threading.enumerate()]
        assert names.count(interp_probe.THREAD_NAME) == 1
        trace.configure(enabled=True)  # armed twice: still one probe
        assert [t.name for t in threading.enumerate()].count(
            interp_probe.THREAD_NAME
        ) == 1
        time.sleep(0.05)
        assert native._lib.sn_probe_head() > head
    finally:
        trace.configure(enabled=False)
    assert not interp_probe.running()
    # no thread more than before (an earlier file's servers may still be
    # winding theirs down: fewer is no fault of the probe's)
    after = [t.name for t in threading.enumerate()]
    assert interp_probe.THREAD_NAME not in after and set(after) <= set(before)
    head = native._lib.sn_probe_head()
    time.sleep(0.02)
    assert native._lib.sn_probe_head() == head
    n = len(probe_docs())
    time.sleep(0.25)
    assert len(probe_docs()) == n  # and no span closes disarmed


def test_probe_spans_close_every_interval_with_samples_and_cpu_by_class(armed):
    """Nothing here assumes an idle machine: the spinners run until five
    intervals have closed beside them, however long a starved probe takes
    for that, and their CPU is held against the process's own in those
    intervals, not against wall time."""
    stop = threading.Event()

    def spin() -> None:
        while not stop.is_set():
            python_spin(0.01)

    unnamed = threading.Thread(target=spin)
    worker = threading.Thread(target=spin, name="http-pool-volume-7")
    t_begin = time.perf_counter_ns()
    unnamed.start()
    worker.start()
    t_spinning = time.perf_counter_ns()
    # an interval that began before both spun, then five beside them
    wait_for_spans(len(probe_docs()) + 6, timeout=30.0)
    t_stopping = time.perf_counter_ns()
    stop.set()
    unnamed.join()
    worker.join()
    docs = probe_docs()
    wall_ns = time.perf_counter_ns() - t_begin
    # one span an interval, end to end
    assert 5 <= len(docs) <= wall_ns // interp_probe.INTERVAL_NS + 1
    for a, b in zip(docs, docs[1:]):
        assert abs(b["start_ns"] - a["end_ns"]) < 5 * MS
    for d in docs:
        attrs = d["attrs"]
        assert d["op"] == interp_probe.SPAN_OP and not d["children"]
        assert d["thread"] == interp_probe.THREAD_NAME
        assert d["end_ns"] - d["start_ns"] >= interp_probe.INTERVAL_NS
        for key, sample_key in (
            ("py_wait_ns", "py_samples"), ("core_wait_ns", "core_samples"),
        ):
            got = attrs[sample_key]
            assert attrs[key] == interp_probe.summary([w for _t, w in got])
            assert set(attrs[key]) == {"count", "sum", "p50", "p95", "max"}
            assert attrs[key]["count"] == len(got) > 0
            assert all(w >= 0 for _t, w in got)
        # the Python probe's samples are the span's own interval
        assert all(d["start_ns"] <= t <= d["end_ns"] for t, _w in attrs["py_samples"])
        cpu = attrs["cpu_ns"]
        assert set(cpu) >= {"probe", "other_python", "native"}
        assert all(v >= 0 for v in cpu.values())
        assert sum(cpu.values()) >= attrs["process_cpu_ns"] - 30 * MS
    # the intervals that lie wholly beside both spinners: a class has CPU
    # booked only while a thread of it lives
    spun = [
        d for d in docs
        if d["start_ns"] >= t_spinning and d["end_ns"] <= t_stopping
    ]
    assert len(spun) >= 4
    assert all("http_workers" in d["attrs"]["cpu_ns"] for d in spun)
    total = {
        cls: sum(d["attrs"]["cpu_ns"].get(cls, 0) for d in spun)
        for cls in ("probe", "other_python", "http_workers", "pipe_reader")
    }
    process = sum(d["attrs"]["process_cpu_ns"] for d in spun)
    # two spinners share one interpreter: each burns its share of it,
    # of whatever the machine gave the process
    assert total["other_python"] > 0.25 * process, (total, process)
    assert total["http_workers"] > 0.25 * process, (total, process)
    assert total["pipe_reader"] == 0
    assert 0 < total["probe"] < total["other_python"]
    assert process >= total["other_python"] + total["http_workers"] - 30 * MS


def test_other_python_grows_only_while_the_unnamed_thread_spins(armed):
    time.sleep(0.25)
    quiet = probe_docs()
    t = threading.Thread(target=python_spin, args=(0.3,))
    t.start()
    t.join()
    time.sleep(0.12)
    docs = wait_for_spans(len(quiet) + 3)
    spun = docs[len(quiet):]
    other = lambda ds: sum(d["attrs"]["cpu_ns"].get("other_python", 0) for d in ds)
    assert other(spun) > 200 * MS
    assert other(quiet) < 50 * MS  # the test's own thread, asleep


@pytest.mark.parametrize("name,cls", [
    ("http-pool-volume-0", "http_workers"),
    ("http-pool-s3-31", "http_workers"),
    ("http-accept-volume", "http_accept"),
    ("ec-pipe-reader", "pipe_reader"),
    ("ec-pipe-sink", "pipe_sink"),
    ("grpc-volume_3", "rpc"),
    ("shard-net-conn-18080", "shard_plane"),
    ("ec-peer-fetch_7", "peer_fetch"),
    ("shard-net-plane", "other_python"),  # the acceptor: its target classes it
    (interp_probe.THREAD_NAME, "probe"),
    ("Thread-12 (work)", "other_python"),
    ("MainThread", "other_python"),
])
def test_a_threads_class_is_read_from_its_name(name, cls):
    assert interp_probe.thread_class(threading.Thread(name=name)) == cls


def test_an_unnamed_thread_of_the_program_is_not_other_python():
    from seaweedfs_tpu.utils import http_pool

    t = threading.Thread(target=http_pool.PooledHTTPServer.shutdown)
    assert t.name.startswith("Thread-")
    assert interp_probe.thread_class(t) == "program_other"
    assert interp_probe.thread_class(threading.Thread(target=time.sleep)) == "other_python"


def test_waits_and_cpu_are_on_metrics(armed):
    wait_for_spans(2)
    text = M.REGISTRY.render().decode()
    assert 'sw_interp_wait_seconds_bucket{le="0.001"}' in text
    assert 'sw_core_wait_seconds_bucket{le="0.001"}' in text
    assert 'sw_thread_cpu_seconds_total{cls="probe"}' in text
    (key,) = [k for k in interp_probe._interp_wait.snapshot()]
    _counts, total, _sum = interp_probe._interp_wait.snapshot()[key]
    assert total >= sum(d["attrs"]["py_wait_ns"]["count"] for d in probe_docs())


# ------------------------------------------------------ the seam stamps


def _batch_pread(tmp_path) -> None:
    path = tmp_path / "rows"
    path.write_bytes(bytes(range(256)) * 64)
    fd = os.open(path, os.O_RDONLY)
    try:
        dst = np.zeros((2, 4096), np.uint8)
        native.batch_pread([fd, fd], [0, 4096], dst)
        assert dst[1, 1] == 1
    finally:
        os.close(fd)


def _sendv(tmp_path) -> None:
    a, b = socket.socketpair()
    try:
        assert native.sendv(a.fileno(), [b"head", np.arange(8, dtype=np.uint8)]) == 12
        assert b.recv(64) == b"head" + bytes(range(8))
    finally:
        a.close()
        b.close()


def _crc_of_rows(tmp_path) -> None:
    rows = np.arange(8192, dtype=np.uint32).view(np.uint8).reshape(2, -1)
    out = native.crc32c_granules(rows, 4096)
    assert out[0, 0] == native.crc32c(rows[0, :4096])


def _sink_append(tmp_path) -> None:
    fds = [os.open(tmp_path / f"s{i}", os.O_CREAT | os.O_WRONLY) for i in range(2)]
    sink = native.NativeSink(fds, 4096)
    try:
        rows = np.full((2, 4096), 5, np.uint8)
        crcs, counts = np.zeros((2, 4), np.uint32), np.zeros(2, np.int32)
        sink.append(
            [rows[0].ctypes.data, rows[1].ctypes.data], 4096, crcs, counts,
            np.zeros((2, 4), np.uint32), np.zeros(2, np.int32),
        )
        assert list(counts) == [1, 1] and crcs[0, 0] == native.crc32c(rows[0])
    finally:
        sink.destroy()
        for fd in fds:
            os.close(fd)


SEAMS = [_batch_pread, _sendv, _crc_of_rows, _sink_append]


@pytest.mark.parametrize("call", SEAMS, ids=lambda f: f.__name__.strip("_"))
def test_a_native_seam_books_its_return_on_the_ambient_span(armed, tmp_path, call):
    sp = trace.start("http.volume", name="GET /1,01")
    with trace.activate(sp):
        call(tmp_path)
        call(tmp_path)
    trace.finish(sp)
    attrs = trace.traces(op="http.volume")[-1]["attrs"]
    assert attrs["interp_returns"] == 2
    # from the C side's last clock reading to the wrapper's first: a
    # return costs something, and alone it costs little
    assert 0 < attrs["interp_wait_ns"] < 50 * MS


def test_a_seam_under_an_open_stage_books_on_that_stages_span(armed, tmp_path):
    """The pipeline's reader and sink have no ambient span: theirs is
    the one whose stage they opened."""
    root = trace.start("ec.rebuild")
    got = []

    def reader() -> None:
        with trace.stage(root, "disk_read"):
            _batch_pread(tmp_path)
        got.append(True)

    t = threading.Thread(target=reader, name="ec-pipe-reader")
    t.start()
    t.join()
    trace.finish(root)
    attrs = trace.traces(op="ec.rebuild")[-1]["attrs"]
    assert got and attrs["interp_returns"] == 1 and attrs["interp_wait_ns"] > 0


def test_a_seam_with_no_span_books_nothing_and_does_not_raise(armed, tmp_path):
    for call in SEAMS:
        call(tmp_path)
    assert not trace.traces(op="http.volume")


@pytest.mark.parametrize("call", SEAMS, ids=lambda f: f.__name__.strip("_"))
def test_disarmed_a_seam_stamps_nothing(disarmed, tmp_path, call):
    sp = trace.Span("http.volume")  # a span someone kept from armed times
    with trace.activate(sp):
        call(tmp_path)
    assert "interp_returns" not in sp.attrs and "interp_wait_ns" not in sp.attrs


def test_a_spinner_between_stamp_and_return_shows_in_the_booked_wait(armed, tmp_path):
    """What the stamp is for: a worker that comes back from native work
    while another thread holds the interpreter waits a switch interval,
    and that wait is booked to its span."""
    stop = threading.Event()
    spinner = threading.Thread(
        target=lambda: [python_spin(0.005) for _ in iter(stop.is_set, True)]
    )
    sp = trace.start("http.volume")
    spinner.start()
    try:
        # a call long enough for the spinner to take the interpreter
        rows = np.zeros((2, 4 << 20), np.uint8)
        with trace.activate(sp):
            for _ in range(20):
                native.crc32c_granules(rows, 4096)
    finally:
        stop.set()
        spinner.join()
    trace.finish(sp)
    attrs = trace.traces(op="http.volume")[-1]["attrs"]
    assert attrs["interp_returns"] == 20
    assert attrs["interp_wait_ns"] / 20 > 1 * MS, attrs


# ------------------------------------------------- a server's threads


def test_no_thread_a_master_and_a_volume_server_start_is_other_python(tmp_path, disarmed):
    """`other_python` is what the program did NOT start: its own threads
    carry a class by name, or are known by their target's module."""
    from conftest import allocate_port
    from seaweedfs_tpu.server.master import MasterServer
    from seaweedfs_tpu.server.volume_server import VolumeServer

    before = set(threading.enumerate())
    mport = allocate_port()
    master = MasterServer(ip="localhost", port=mport)
    master.start()
    vs = VolumeServer(
        directories=[str(tmp_path)], master=f"localhost:{mport}", ip="localhost",
        port=allocate_port(), max_volume_count=2,
    )
    vs.start()
    try:
        deadline = time.time() + 10
        while not master.topo.nodes and time.time() < deadline:
            time.sleep(0.05)
        started = [t for t in threading.enumerate() if t not in before]
        classes = {t.name: interp_probe.thread_class(t) for t in started}
        # gRPC's own threads (its server loop, its channels' pollers)
        # are the library's, not the program's
        stray = {
            t.name: t._target.__module__ for t in started
            if classes[t.name] == "other_python"
            and not t._target.__module__.startswith("grpc.")
        }
        assert not stray, classes
        assert "http_workers" in classes.values()
        assert classes["http-accept-volume"] == "http_accept"
        assert "rpc" in classes.values()  # the heartbeat's stream
    finally:
        vs.stop()
        master.stop()


# ------------------------------------------------------------- the ring


def test_probe_spans_never_push_an_operations_trace_out_of_the_ring(disarmed):
    """Ten a second, for as long as the tracer is armed: they have a ring
    of their own, of the configured size, and come first in traces()."""
    trace.configure(enabled=True, ring_size=4, slow_op_s=0.0)
    trace.reset()
    try:
        trace.finish(trace.start("ec.encode", name="kept"))
        docs = wait_for_spans(7)
        assert len(probe_docs()) == 4  # the probe ring is bounded too
        every = trace.traces()
        assert [d["name"] for d in every if d["op"] != interp_probe.SPAN_OP] == ["kept"]
        assert every[-1]["name"] == "kept"  # the newest real root stays last
        assert all(d["span_count"] == 1 for d in docs)
    finally:
        trace.configure(enabled=False, ring_size=256)
    trace.reset()
    assert trace.traces() == []
