"""Flight-recorder (utils/trace.py) correctness + metrics-registry
hardening.

Covers the ISSUE-7 trace contracts: span-tree invariants over real EC
ops (children nested in the root's wall time, per-stage totals bounded
by the op duration), the disarmed no-allocation fast path, overlap-
efficiency math, Chrome trace_event export, the slow-op log, gRPC
metadata continuity, and the Prometheus text-format hardening
(label escaping roundtrip, duplicate-registration guard, package-wide
metric naming lint).
"""

from __future__ import annotations

import importlib
import json
import pkgutil
import re
import time

import os

import pytest

from seaweedfs_tpu.ec import CpuBackend, EcVolume, ec_encode_volume, rebuild_ec_files
from seaweedfs_tpu.utils import metrics as M
from seaweedfs_tpu.utils import request_id as rid
from seaweedfs_tpu.utils import trace

from test_ec_pipeline import CTX, make_volume


@pytest.fixture
def recorder():
    trace.configure(enabled=True, ring_size=256, slow_op_s=0.0)
    trace.reset()
    yield trace
    trace.configure(enabled=False, slow_op_s=0.0)
    trace.reset()


def walk(doc):
    yield doc
    for ch in doc["children"]:
        yield from walk(ch)


# ---------------------------------------------------------------- disarmed


def test_disarmed_fast_path_is_noop_singleton(disarmed):
    """Span-enter/exit when disarmed must be one flag/is-None check and
    ZERO allocations: every helper returns the same singleton or None."""
    assert not trace.armed
    assert trace.start("ec.encode") is None
    assert trace.current() is None
    noop = trace.stage(None, "disk_read")
    assert noop is trace.stage(None, "h2d_dispatch")
    assert noop is trace.activate(None)
    with noop:
        pass
    # plain no-ops, no exceptions, nothing recorded
    trace.add_stage(None, "disk_read", 1.0)
    trace.event(None, "x", a=1)
    trace.finish(None)
    assert trace.traces() == []
    # disarmed + no active request id: nothing to carry on the wire
    rid.clear()
    assert trace.grpc_metadata() is None
    # ...but an active request id still rides (id propagation is not
    # gated on the tracer)
    rid.ensure("req-123")
    md = dict(trace.grpc_metadata())
    assert md == {trace.REQUEST_ID_KEY: "req-123"}
    rid.clear()


# ------------------------------------------------------- span invariants


def test_span_tree_invariants_on_real_ec_ops(recorder, tmp_path):
    """Encode + degraded read + rebuild under the armed recorder: every
    child span nests inside its root's wall time, every stage total is
    bounded by its span's duration, and the per-op histograms/gauges
    populate."""
    TOL = 0.25  # clock-read ordering slack, generous for slow CI boxes

    base, payloads = make_volume(tmp_path, needles=20)
    ec_encode_volume(base, CTX)

    for i in (0, 3):
        os.unlink(base + CTX.to_ext(i))
    ev = EcVolume(str(tmp_path), 1, backend_name="cpu")
    try:
        for i in list(payloads)[:3]:
            assert ev.read_needle(i).data == payloads[i]
    finally:
        ev.close()

    assert rebuild_ec_files(base, CTX, backend=CpuBackend(CTX)) == [0, 3]

    docs = trace.traces()
    by_op = {}
    for d in docs:
        by_op.setdefault(d["op"], []).append(d)
    assert "ec.encode_volume" in by_op
    assert "ec.degraded_read" in by_op
    assert "ec.rebuild" in by_op

    for root in docs:
        r_lo = root["start_ts"] - TOL
        r_hi = root["start_ts"] + root["duration_s"] + TOL
        for node in walk(root):
            assert node["trace_id"] == root["trace_id"]
            assert node["duration_s"] >= 0.0
            assert node["start_ts"] >= r_lo
            assert node["start_ts"] + node["duration_s"] <= r_hi
            for name, acc in node["stages"].items():
                assert acc["count"] >= 1, (root["op"], name)
                if name == "queue_wait":
                    # accumulated from BOTH pipeline threads (reader's
                    # read_q put + dispatcher's write_q put) — under
                    # two-sided backpressure its total may legitimately
                    # exceed the op wall
                    continue
                # every other stage accumulates non-overlapping timed
                # sections of one thread: total bounded by the op wall
                assert acc["seconds"] <= node["duration_s"] + TOL, (
                    root["op"], name, acc,
                )

    # encode: the volume root carries the pipeline child with the
    # canonical stage set
    enc = by_op["ec.encode_volume"][0]
    pipe = [n for n in walk(enc) if n["op"] == "ec.encode"]
    assert pipe and {"disk_read", "write_sink"} <= set(pipe[0]["stages"])
    # degraded read: sibling reads + sidecar verification attributed
    dr_stages = set()
    for d in by_op["ec.degraded_read"]:
        dr_stages |= set(d["stages"])
    assert "sibling_read" in dr_stages
    # rebuild: published via fsync/rename windows
    rb = by_op["ec.rebuild"][0]
    assert "fsync_publish" in rb["stages"]

    text = M.REGISTRY.render().decode()
    for op in ("ec.encode", "ec.degraded_read", "ec.rebuild"):
        assert f'op="{op}"' in text
    assert "sw_ec_stage_seconds_count" in text
    assert "sw_ec_overlap_efficiency" in text


def test_ring_is_bounded(recorder):
    trace.configure(ring_size=4)
    for i in range(10):
        trace.finish(trace.start("ec.encode", name=f"op{i}"))
    docs = trace.traces()
    assert len(docs) == 4
    assert docs[-1]["name"] == "op9"  # newest kept, oldest dropped


# --------------------------------------------------------------- overlap


def _doc(dur, stages):
    return {
        "duration_s": dur,
        "stages": {
            k: {"seconds": v, "count": 1, "chip": ""}
            for k, v in stages.items()
        },
        "children": [],
    }


def test_overlap_efficiency_math():
    # fully serial: wall = host + device, every device second exposed
    assert trace.overlap_efficiency(_doc(2.0, {
        "disk_read": 1.0, "h2d_dispatch": 0.5, "device_drain": 0.5,
    })) == 0.0
    # fully overlapped: wall = host alone and the drain never blocked
    assert trace.overlap_efficiency(_doc(1.0, {
        "disk_read": 1.0, "h2d_dispatch": 0.5, "device_drain": 0.0,
    })) == 1.0
    # half hidden: residue and measured drain agree at device/2
    assert trace.overlap_efficiency(_doc(1.25, {
        "disk_read": 1.0, "h2d_dispatch": 0.25, "device_drain": 0.25,
    })) == pytest.approx(0.5)
    # host stages overlapping EACH OTHER (reader + sink threads): their
    # sum exceeds wall, zeroing the residue — but a 0.9s measured drain
    # is exposed by definition, so the gauge must NOT saturate at 1.0
    assert trace.overlap_efficiency(_doc(1.1, {
        "disk_read": 1.0, "write_sink": 1.0,
        "h2d_dispatch": 0.1, "device_drain": 0.9,
    })) == pytest.approx(0.1)
    # no device work: undefined, not 0 (an op class with no device time
    # must not drag the gauge)
    assert trace.overlap_efficiency(_doc(1.0, {"disk_read": 1.0})) is None


# ---------------------------------------------------------------- export


def test_chrome_trace_export_structure(recorder):
    sp = trace.start("ec.encode", name="vol1", base="/x/1")
    with trace.activate(sp):
        with trace.stage(sp, "disk_read"):
            pass
        child = trace.start("ec.peer_fetch", name="shard 2")
        trace.event(child, "placement", chip="chip0")
        trace.finish(child)
    trace.finish(sp)

    doc = trace.chrome_trace()
    json.loads(json.dumps(doc))  # serializable
    evs = doc["traceEvents"]
    metas = [e for e in evs if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in metas)
    assert any(e["name"] == "thread_name" for e in metas)
    xs = [e for e in evs if e["ph"] == "X"]
    # the two spans, and the stage's interval as an event of its own
    assert {e["name"] for e in xs} == {"vol1", "shard 2", "disk_read"}
    for e in xs:
        assert e["dur"] > 0 and e["ts"] > 0
        assert {"pid", "tid", "cat", "args"} <= set(e)
    root_ev = next(e for e in xs if e["name"] == "vol1")
    stage_ev = next(e for e in xs if e["name"] == "disk_read")
    assert stage_ev["cat"] == "ec.encode"
    assert root_ev["ts"] <= stage_ev["ts"]
    assert stage_ev["ts"] + stage_ev["dur"] <= root_ev["ts"] + root_ev["dur"] + 1
    assert root_ev["args"]["trace_id"] == sp.trace_id
    assert "disk_read" in root_ev["args"]["stages_ms"]
    assert any(e["ph"] == "i" and e["name"] == "placement" for e in evs)
    # filtering by an unknown trace id yields an empty event list
    assert trace.chrome_trace("feedfeedfeedfeed")["traceEvents"] == []


def test_grpc_metadata_continuity(recorder):
    """Client-side metadata -> server-side adoption keeps ONE trace id
    with parent/child linkage, the wire-format contract behind the
    cross-server tests in test_ec_cluster_chaos.py."""
    rid.ensure("req-xyz")
    try:
        sp = trace.start("ec.peer_rebuild", name="v7")
        with trace.activate(sp):
            md = dict(trace.grpc_metadata())
        assert md[trace.TRACE_ID_KEY] == sp.trace_id
        assert md[trace.PARENT_SPAN_KEY] == sp.span_id
        assert md[trace.REQUEST_ID_KEY] == "req-xyz"
        adopted = trace.start_from_metadata(
            "rpc.ec_shard_read", md, server="peer:8080"
        )
        assert adopted.trace_id == sp.trace_id
        assert adopted.parent_id == sp.span_id
        assert adopted.server == "peer:8080"
        trace.finish(adopted)
        trace.finish(sp)
        tid_docs = trace.traces(sp.trace_id)
        assert len(tid_docs) == 2  # two local roots, one logical trace
    finally:
        rid.clear()


def test_slow_op_log_fires_above_its_threshold_only(recorder, capfd):
    trace.configure(slow_op_s=0.001)
    sp = trace.start("ec.rebuild", name="slowpoke")
    with trace.stage(sp, "disk_read"):
        time.sleep(0.01)
    trace.finish(sp)
    err = capfd.readouterr().err
    assert "slow op ec.rebuild" in err
    assert "slowpoke" in err and "disk_read" in err and "cpu=" in err
    # below threshold: quiet
    trace.configure(slow_op_s=5.0)
    trace.finish(trace.start("ec.rebuild", name="fast"))
    assert "slow op" not in capfd.readouterr().err


# --------------------------------------- intervals, CPU time, sub-stages


def _jax_rebuild(tmp_path, needles=40):
    """An armed `ec.rebuild` of two shards on a one-device JaxBackend
    (the CPU's): the staged pipeline with its three threads."""
    from seaweedfs_tpu.ec.backend import JaxBackend

    base, _payloads = make_volume(tmp_path, needles=needles)
    ec_encode_volume(base, CTX)
    for i in (0, 3):
        os.unlink(base + CTX.to_ext(i))
    trace.reset()
    assert rebuild_ec_files(
        base, CTX, backend=JaxBackend(CTX, n_devices=1)
    ) == [0, 3]
    return next(d for d in trace.traces() if d["op"] == "ec.rebuild")


def test_intervals_nest_in_their_span_and_sub_stages_in_their_parent(
    recorder, tmp_path
):
    doc = _jax_rebuild(tmp_path)
    seen = set()
    for node in walk(doc):
        assert node["start_ns"] < node["end_ns"]
        assert node["duration_s"] == pytest.approx(
            (node["end_ns"] - node["start_ns"]) / 1e9
        )
        per_stage: dict = {}
        for stage, t0, t1, thread, cpu_ns in node["intervals"]:
            seen.add(stage)
            assert node["start_ns"] <= t0 <= t1 <= node["end_ns"], stage
            assert thread and cpu_ns >= -1
            per_stage.setdefault(stage, []).append((t0, t1, thread))
        assert node["stages_dropped"] == 0
        # the accumulators are what they were: one count an interval
        for stage, acc in node["stages"].items():
            assert acc["count"] == len(per_stage[stage]), stage
        for stage in per_stage:
            if stage not in trace.SUB_STAGES:
                continue
            parent = stage.rsplit(".", 1)[0]
            for t0, t1, thread in per_stage[stage]:
                assert any(
                    p0 <= t0 and t1 <= p1 and pt == thread
                    for p0, p1, pt in per_stage[parent]
                ), (stage, "lies outside every", parent)
    assert {
        "disk_read", "h2d_dispatch", "device_drain", "write_sink",
        "h2d_dispatch.stage", "h2d_dispatch.put", "h2d_dispatch.launch",
        "device_drain.ready", "device_drain.d2h", "device_drain.host_copy",
    } <= seen
    # the pipeline's three threads each have a name of their own
    threads = {
        iv[3] for node in walk(doc) for iv in node["intervals"]
    }
    assert {"ec-pipe-reader", "ec-pipe-sink"} <= threads and len(threads) >= 3
    # the parts of one entry lie end to end and end with their parent:
    # they make it up but for what ran before the first
    pipe = next(n for n in walk(doc) if "device_drain" in n["stages"])
    ivs = [iv for iv in pipe["intervals"] if iv[0].startswith("device_drain")]
    for i, iv in enumerate(ivs):
        if iv[0] == "device_drain":  # recorded at its exit, after its parts
            ready, d2h, host_copy = ivs[i - 3 : i]
            assert [ready[0], d2h[0], host_copy[0]] == [
                "device_drain.ready", "device_drain.d2h", "device_drain.host_copy"
            ]
            assert ready[2] == d2h[1] and d2h[2] == host_copy[1]
            assert host_copy[2] == iv[2] and iv[1] <= ready[1]
    st = pipe["stages"]
    for parent in ("device_drain", "h2d_dispatch"):
        parts = sum(
            a["seconds"] for name, a in st.items() if name.startswith(parent + ".")
        )
        assert parts <= st[parent]["seconds"] <= parts + 0.002 * st[parent]["count"]
    # what crossed the seam, counted where it crossed
    attrs = next(n for n in walk(doc) if "h2d_bytes" in n["attrs"])["attrs"]
    assert attrs["batches"] >= 1
    assert attrs["h2d_bytes"] == 5 * attrs["d2h_bytes"]  # 10 rows in, 2 out
    text = M.REGISTRY.render().decode()
    assert 'sw_ec_h2d_bytes_total{op="ec.rebuild"}' in text
    assert 'sw_ec_device_batches_total{op="ec.rebuild"}' in text


def test_interval_cap_holds_and_stages_dropped_counts(recorder, monkeypatch):
    monkeypatch.setattr(trace, "MAX_INTERVALS", 4)
    sp = trace.start("ec.encode")
    for _ in range(7):
        with trace.stage(sp, "disk_read"):
            pass
    sp.add_stage("queue_wait", 0.25)
    trace.finish(sp)
    doc = trace.traces()[-1]
    assert len(doc["intervals"]) == 4
    assert doc["stages_dropped"] == 4
    # past the cap the accumulators still grow
    assert doc["stages"]["disk_read"]["count"] == 7
    assert doc["stages"]["queue_wait"]["seconds"] == 0.25
    span_ev = next(
        e for e in trace.chrome_trace()["traceEvents"] if e.get("cat") == e["name"]
    )
    assert span_ev["args"]["stages_dropped"] == 4


def test_an_after_the_fact_stage_ends_now_and_has_no_cpu_reading(recorder):
    sp = trace.start("rpc.ec_shard_read")
    t0 = time.perf_counter_ns()
    trace.add_stage(sp, "stream", 0.5)
    t1 = time.perf_counter_ns()
    trace.finish(sp)
    (stage, start, end, _thread, cpu_ns), = trace.traces()[-1]["intervals"]
    assert stage == "stream" and cpu_ns == -1
    assert t0 <= end <= t1 and end - start == 500_000_000


def test_totals_with_sub_stages_equal_totals_without(recorder):
    with_parts = _doc(2.0, {
        "disk_read": 1.0, "h2d_dispatch": 0.5, "device_drain": 0.5,
        "h2d_dispatch.put": 0.3, "h2d_dispatch.launch": 0.2,
        "device_drain.ready": 0.4, "device_drain.d2h": 0.1,
        "reconstruct": 0.1, "reconstruct.ready": 0.1,
    })
    without = _doc(2.0, {
        "disk_read": 1.0, "h2d_dispatch": 0.5, "device_drain": 0.5,
        "reconstruct": 0.1,
    })
    assert trace._tree_stage_totals(with_parts) == trace._tree_stage_totals(without)
    assert trace.overlap_efficiency(with_parts) == trace.overlap_efficiency(without)
    # every sub-stage names a parent that is a stage, and is one itself
    for name in trace.SUB_STAGES:
        parent = name.rsplit(".", 1)[0]
        assert parent in trace.STAGES and parent not in trace.SUB_STAGES
        assert name in trace.STAGES
    # the histogram an operator sums over `stage` has no sub-stage series
    sp = trace.start("ec.subtotal_probe")
    with trace.stage(sp, "h2d_dispatch"):
        trace.lap("put")
        trace.lap("no_such_part")  # not a part: not recorded, `put` goes on
    trace.lap("put")  # no stage open: not recorded
    trace.finish(sp)
    doc = trace.traces()[-1]
    assert set(doc["stages"]) == {"h2d_dispatch", "h2d_dispatch.put"}
    text = M.REGISTRY.render().decode()
    assert 'op="ec.subtotal_probe",stage="h2d_dispatch"' in text
    assert 'op="ec.subtotal_probe",stage="h2d_dispatch.put"' not in text
    assert "ec.subtotal_probe/h2d_dispatch.put" not in trace.stage_ewmas()
    assert "ec.subtotal_probe/h2d_dispatch" in trace.stage_ewmas()


def test_cpu_time_is_work_and_wall_less_cpu_is_waiting(recorder):
    SLACK = 0.05
    sp = trace.start("ec.encode")
    with trace.stage(sp, "verify"):  # a busy loop: CPU near its wall
        c0 = time.thread_time()
        while time.thread_time() - c0 < 0.05:
            pass
    with trace.stage(sp, "disk_read"):  # blocked: wall without CPU
        time.sleep(0.1)
    trace.finish(sp)
    doc = trace.traces()[-1]
    st = doc["stages"]
    assert 0.05 <= st["verify"]["cpu_s"] <= st["verify"]["seconds"] + SLACK
    assert st["disk_read"]["seconds"] >= 0.1
    assert st["disk_read"]["cpu_s"] < 0.03
    assert 0.05 <= doc["cpu_s"] <= doc["duration_s"] + SLACK
    assert doc["duration_s"] - doc["cpu_s"] >= 0.07  # the sleep shows as waiting
    for _stage, t0, t1, _thread, cpu_ns in doc["intervals"]:
        assert 0 <= cpu_ns <= (t1 - t0) + SLACK * 1e9
    # a span finished by another thread has no CPU reading to give
    import threading

    other = trace.start("ec.encode")
    t = threading.Thread(target=trace.finish, args=(other,))
    t.start()
    t.join(10)
    assert not t.is_alive()
    assert trace.traces()[-1]["cpu_s"] is None
    assert "cpu=" in trace.format_tree(doc)


def test_admission_wait_is_a_with_block_charged_the_tickets_wait(recorder):
    """The queue's own `wait_s` (its injectable clock) stays the stage's
    seconds; the interval is the with-block's."""
    from seaweedfs_tpu.ec.device_queue import DeviceQueue

    clock = iter(float(i) for i in range(100))
    q = DeviceQueue(window=2, clock=lambda: next(clock), label="chipX")
    sp = trace.start("ec.degraded_read")
    with q.admission("foreground", 10, span=sp) as ticket:
        pass
    stream = q.stream("recovery", span=sp)
    ticket2, handle = stream.dispatch(lambda: "handle", 10)
    stream.release(ticket2)
    trace.finish(sp)
    doc = trace.traces()[-1]
    acc = doc["stages"]["admission_wait"]
    assert acc["count"] == 2 and acc["chip"] == "chipX"
    assert acc["seconds"] == ticket.wait_s + ticket2.wait_s > 1.0  # fake seconds
    waits = [iv for iv in doc["intervals"] if iv[0] == "admission_wait"]
    assert all((t1 - t0) / 1e9 < 0.5 for _s, t0, t1, _t, _c in waits)  # real ones
    assert handle == "handle" and "h2d_dispatch" in doc["stages"]


def test_a_profiler_trace_holds_the_programs_stages(recorder, tmp_path):
    """A `jax.profiler` trace taken by anyone around an armed operation
    holds `sw:<op>/<stage>` events on a host plane, on the clock of the
    device operations (here the CPU backend's)."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        doc = _jax_rebuild(tmp_path, needles=140)  # some 8 MiB of shards
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        str(tmp_path / "prof" / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    names: dict[str, int] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                # the encode before it left its own (sw:ec.encode/...)
                if ev.name.startswith("sw:ec.rebuild"):
                    names[ev.name] = names.get(ev.name, 0) + 1
                    assert dict(ev.stats)["trace_id"] == "t" + doc["trace_id"]
    assert names["sw:ec.rebuild"] == 1  # no ids in a name: one name an op
    for stage in ("disk_read", "h2d_dispatch", "h2d_dispatch.put",
                  "device_drain", "device_drain.ready", "write_sink"):
        counted = sum(
            n["stages"].get(stage, {"count": 0})["count"] for n in walk(doc)
        )
        # the reader's last, empty read is annotated though not counted
        assert counted <= names[f"sw:ec.rebuild/{stage}"] <= counted + 1, stage


def test_the_programs_compile_counter_agrees_with_jax_monitoring(recorder):
    import jax
    import numpy as np

    from seaweedfs_tpu.ec import backend as B

    seen = []

    def listener(event, seconds, **_kw):
        if event == B._COMPILE_EVENT:
            seen.append(seconds)

    def counter(name):
        for ln in M.REGISTRY.render().decode().splitlines():
            if ln.startswith(name + " "):
                return float(ln.rsplit(" ", 1)[1])
        return 0.0

    be = B.JaxBackend(CTX, n_devices=1)  # registers the program's listener
    B.JaxBackend(CTX, n_devices=1)  # ...once, however many backends
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        n0, s0 = counter("sw_ec_compiles_total"), counter("sw_ec_compile_seconds_total")
        sp = trace.start("ec.degraded_read")
        with trace.activate(sp):
            # a width no other test uses: a cold apply compiles
            data = np.arange(10 * 7919, dtype=np.uint8).reshape(10, 7919)
            out = be.apply(np.eye(2, 10, dtype=np.uint8), data)
        trace.finish(sp)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert np.array_equal(out, data[:2])
    assert len(seen) >= 1
    assert counter("sw_ec_compiles_total") - n0 == len(seen)
    assert counter("sw_ec_compile_seconds_total") - s0 == pytest.approx(sum(seen))
    # the span that paid says so
    events = [e for e in trace.traces()[-1]["events"] if e["name"] == "compile"]
    assert len(events) == len(seen)
    # disarmed the count goes on (an operator's), the event does not
    trace.configure(enabled=False)
    data = np.zeros((10, 7927), dtype=np.uint8)
    be.apply(np.eye(2, 10, dtype=np.uint8), data)
    assert counter("sw_ec_compiles_total") - n0 > len(seen)


def test_pooled_http_root_starts_where_the_worker_took_the_request(recorder):
    """`ready_wait` ends where the root span starts, `parse` starts
    there, `send` lies inside; none of it without the pooled server's
    stamps (a threaded server's handler blocks in readline between
    keep-alive requests, which is no part of any request)."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    import requests

    from seaweedfs_tpu.utils.http_pool import PooledHTTPServer, send_body

    class H(rid.RequestTracingMixin, BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"
        trace_server_kind = "volume"

        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b"x" * 20_000
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            send_body(self, body)

    def serve(srv, n):
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            sess = requests.Session()
            for _ in range(n):
                r = sess.get(f"http://127.0.0.1:{srv.server_address[1]}/a")
                assert len(r.content) == 20_000
            deadline = time.time() + 10
            while len(trace.traces(op="http.volume")) < n:
                assert time.time() < deadline
                time.sleep(0.01)
        finally:
            srv.shutdown()
            srv.server_close()
        docs = trace.traces(op="http.volume")
        trace.reset()
        return docs

    docs = serve(PooledHTTPServer(("127.0.0.1", 0), H, workers=2, server_kind="volume"), 3)
    # the first request of a connection always comes through the ready
    # queue; a later one may find its worker still there
    assert "ready_wait" in docs[0]["stages"]
    for doc in docs:
        by = {iv[0]: iv for iv in doc["intervals"]}
        assert {"parse", "send"} <= set(by)
        assert doc["start_ns"] == by["parse"][1]
        if "ready_wait" in by:
            assert by["ready_wait"][1] <= by["ready_wait"][2] == doc["start_ns"]
        assert by["parse"][2] <= by["send"][1] <= by["send"][2] <= doc["end_ns"]
        assert doc["cpu_s"] is not None and doc["cpu_s"] <= doc["duration_s"] + 0.05
        assert doc["thread"].startswith("http-pool-volume-")
    docs = serve(ThreadingHTTPServer(("127.0.0.1", 0), H), 2)
    for doc in docs:
        assert "parse" not in doc["stages"] and "ready_wait" not in doc["stages"]
        assert doc["duration_s"] < 5.0


# ------------------------------------------------------- disarm discipline


class _NoClock:
    """Stands in for the `time` module: any clock read is a fault."""

    def __getattr__(self, name):
        raise AssertionError(f"time.{name} read while the tracer is disarmed")


def test_disarmed_no_new_site_reads_a_clock_or_allocates(disarmed, monkeypatch, tmp_path):
    """The sites this PR added, driven with the tracer off: each is one
    module-bool or is-None check that hands back the no-op singleton."""
    import numpy as np

    from seaweedfs_tpu.ec.backend import JaxBackend
    from seaweedfs_tpu.ec.device_queue import DeviceQueue
    from seaweedfs_tpu.ec.pipeline import run_staged_apply

    assert not trace.armed
    noop = trace.stage(None, "disk_read")
    assert trace.lap("put") is None and trace.lap("ready") is None
    assert trace.turn("request_rtt") is None
    with trace.stage(None, "admission_wait") as timer:
        timer.seconds = 1.0  # accepted, ignored, nothing stored
        timer.drop()
    assert timer is noop and timer.seconds is None
    assert trace.count("h2d_bytes", 1) is None
    be = JaxBackend(CTX, n_devices=1)
    data = np.arange(10 * 4096, dtype=np.uint8).reshape(10, 4096)
    coeffs = np.eye(2, 10, dtype=np.uint8)
    be.to_host(be.apply_staged(coeffs, be.to_device(data)))  # compiled here
    monkeypatch.setattr(trace, "time", _NoClock())
    monkeypatch.setattr(trace, "_StageTimer", None)  # constructing one fails
    monkeypatch.setattr(trace, "_annotate", None)
    got = []
    run_staged_apply(
        be, coeffs, lambda: iter([(0, data), (1, data)]),
        lambda tag, out: got.append((tag, out.copy())),
        device_queue=DeviceQueue(window=2), span=None,
    )
    assert [t for t, _ in got] == [0, 1]
    assert all(np.array_equal(out, data[:2]) for _t, out in got)
    rec = be.reconstruct({i: data[i] for i in range(10)}, want=[10])
    assert set(rec) == {10}
    assert trace.traces() == []


def test_the_tracer_never_imports_jax():
    """A filer or master process arms the tracer without ever loading
    JAX: annotations appear only where something else already did."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from seaweedfs_tpu.utils import trace, http_pool, request_id\n"
        "trace.configure(enabled=True)\n"
        "sp = trace.start('http.filer')\n"
        "with trace.stage(sp, 'filer.lookup'):\n"
        "    trace.lap('put')\n"
        "trace.finish(sp)\n"
        "assert trace.traces()[-1]['stages']['filer.lookup']['count'] == 1\n"
        "assert 'jax' not in sys.modules, 'the tracer imported jax'\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr


# ------------------------------------------------- metrics hardening


def test_duplicate_metric_registration_raises():
    reg = M.Registry()
    reg.counter("sw_dup_total", "first", ("a",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("sw_dup_total", "second")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("sw_dup_total", "third")


_SAMPLE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})? (\S+)$'
)
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(v: str) -> str:
    return (
        v.replace("\\n", "\n").replace('\\"', '"').replace("\\\\", "\\")
    )


def test_exposition_escaping_roundtrip_adversarial_labels():
    """Scrape a registry holding hostile label values / help text and
    re-parse the text format: every line must lex, and the decoded
    label values must round-trip bit-exact."""
    evil = 'quote:" backslash:\\ newline:\nend'
    reg = M.Registry()
    c = reg.counter(
        "sw_esc_total", 'help with "quotes", \\slashes\n and newline',
        ("lbl",),
    )
    c.inc(lbl=evil)
    c.inc(2, lbl="plain")
    g = reg.gauge("sw_esc_gauge", "g", ("a", "b"))
    g.set(1.5, a="x\\", b='"\n"')
    text = reg.render().decode()

    parsed = {}
    for ln in text.splitlines():
        assert ln.strip(), "blank line inside exposition"
        if ln.startswith("#"):
            # comment lines must stay single-line comments
            assert ln.startswith("# HELP") or ln.startswith("# TYPE")
            continue
        m = _SAMPLE.match(ln)
        assert m, f"unparseable sample line: {ln!r}"
        labels = {
            k: _unescape(v) for k, v in _LABEL.findall(m.group(2) or "")
        }
        parsed[(m.group(1), tuple(sorted(labels.items())))] = float(
            m.group(3)
        )

    assert parsed[("sw_esc_total", (("lbl", evil),))] == 1.0
    assert parsed[("sw_esc_total", (("lbl", "plain"),))] == 2.0
    assert parsed[("sw_esc_gauge", (("a", "x\\"), ("b", '"\n"')))] == 1.5


_STAGE_PATTERNS = [
    # trace.stage(sp, "name") — the first arg may be a call like
    # trace.current()
    re.compile(
        r'\bstage\(\s*[A-Za-z_][\w.\[\]]*(?:\(\))?\s*,\s*"([a-z0-9_.]+)"'
    ),
    # span.stage("name")
    re.compile(r'\.stage\(\s*"([a-z0-9_.]+)"'),
    # span.add_stage("name", secs) — possibly split across lines
    re.compile(r'add_stage\(\s*"([a-z0-9_.]+)"'),
    # trace.add_stage(span, "name", secs)
    re.compile(r'add_stage\(\s*[A-Za-z_][\w.]*\s*,\s*"([a-z0-9_.]+)"'),
    # trace.turn("name"), span.add_interval("name", t0, t1)
    re.compile(r'\bturn\(\s*"([a-z0-9_.]+)"'),
    re.compile(r'add_interval\(\s*"([a-z0-9_.]+)"'),
    # pipeline stage-name kwargs
    re.compile(r'(?:read_stage|write_stage)\s*=\s*"([a-z0-9_.]+)"'),
]
_STAGE_TUPLE = re.compile(r"stage_names\s*=\s*\(([^)]*)\)")


def test_stage_name_registry_lint():
    """Every stage-name literal in the package must be in trace.STAGES:
    a typo'd label would silently fork a sw_ec_stage_seconds series
    (and vanish from the heartbeat EWMAs) instead of failing here."""
    import seaweedfs_tpu

    pkg_root = seaweedfs_tpu.__path__[0]
    found: dict[str, set] = {}
    for dirpath, _dirnames, filenames in os.walk(pkg_root):
        if "__pycache__" in dirpath:
            continue
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            with open(path) as f:
                src = f.read()
            names = set()
            for pat in _STAGE_PATTERNS:
                names.update(pat.findall(src))
            for tup in _STAGE_TUPLE.findall(src):
                names.update(re.findall(r'"([a-z0-9_.]+)"', tup))
            for n in names:
                found.setdefault(n, set()).add(
                    os.path.relpath(path, pkg_root)
                )
    unknown = {
        n: sorted(files)
        for n, files in found.items()
        if n not in trace.STAGES
    }
    assert not unknown, (
        f"stage literals outside trace.STAGES (typo'd histogram "
        f"label?): {unknown}"
    )
    # the scan actually sees the fleet — a broken regex must not pass
    # vacuously (gateway + pipeline stages at minimum)
    assert len(found) >= 12, sorted(found)
    for required in (
        "s3.auth", "filer.lookup", "chunk.fetch", "volume.read",
        "disk_read", "h2d_dispatch", "admission_wait",
        # a read from a peer: turned to, and laid from clock readings
        "conn_checkout", "request_rtt", "payload_land", "fetch_queue",
        "stream.resolve", "stream.sendfile",
    ):
        assert required in found, required


def test_metrics_lint_package_wide():
    """Walk the package, import every module best-effort (optional deps
    may be absent in this container), then lint EVERY sw_* registration:
    unique names, `sw_<subsystem>_<name>` convention, non-empty help,
    counters end in _total, timing histograms in _seconds."""
    import seaweedfs_tpu

    for mod in pkgutil.walk_packages(
        seaweedfs_tpu.__path__, "seaweedfs_tpu."
    ):
        try:
            importlib.import_module(mod.name)
        except Exception:
            continue  # same tolerance as tier-1 collection

    metrics = list(M.REGISTRY._metrics)
    assert len(metrics) >= 15  # the walk actually registered the fleet
    names = [m.name for m in metrics]
    assert len(names) == len(set(names)), "duplicate metric names"
    pat = re.compile(r"^sw(_[a-z0-9]+)+$")
    for m in metrics:
        assert pat.match(m.name), f"bad metric name {m.name!r}"
        assert m.help and m.help.strip(), f"{m.name} has no help text"
        if isinstance(m, M.Counter):
            assert m.name.endswith("_total"), (
                f"counter {m.name} must end in _total"
            )
        if isinstance(m, M.Histogram):
            assert m.name.endswith("_seconds"), (
                f"timing histogram {m.name} must end in _seconds"
            )
