"""The readers of the program's own timeline (stage intervals, CPU
seconds, sub-stages, `sw:` annotations) on span documents and event
lists worked by hand, and on ones recorded from a v5e run."""

import json
import pathlib

import pytest

from ecbench import harness, spanlib

HERE = pathlib.Path(__file__).resolve().parent
GIB = 1 << 30
S = 1_000_000_000  # ns


def reader(name):
    return harness.load_module("layers", name).read


def span(op, t0, t1, thread="main", cpu_s=None, stages=None, intervals=(),
         children=(), attrs=None):
    """A span document as `Span.to_dict()` writes it, times in seconds."""
    return {
        "op": op, "name": op, "start_ns": int(t0 * S), "end_ns": int(t1 * S),
        "duration_s": t1 - t0, "cpu_s": cpu_s, "thread": thread,
        "attrs": attrs or {}, "events": [],
        "stages": {
            s: {"seconds": sec, "count": 1, "chip": "", "cpu_s": 0.0}
            for s, sec in (stages or {}).items()
        },
        "intervals": [
            [s, int(a * S), int(b * S), th, int(cpu * S) if cpu >= 0 else -1]
            for s, a, b, th, cpu in intervals
        ],
        "children": list(children),
    }


def parent_span(op, duration_s, stages=None, children=(), attrs=None):
    """What the program wrote before it kept intervals."""
    doc = span(op, 0.0, duration_s, stages=stages, children=children, attrs=attrs)
    for key in ("start_ns", "end_ns", "cpu_s", "thread", "intervals"):
        del doc[key]
    return doc


def rebuild_obs():
    """One 1 GiB operation of 2 s: the reader reads 0-0.5 and 0.6-1.1,
    the sink waits for the device 0.5-1.0 and 1.2-1.9."""
    pipe = span(
        "ec.rebuild", 0.1, 1.95, thread="rpc", cpu_s=0.40,
        stages={
            "disk_read": 1.0, "h2d_dispatch": 0.30, "h2d_dispatch.stage": 0.01,
            "h2d_dispatch.put": 0.19, "h2d_dispatch.launch": 0.09,
            "device_drain": 1.2, "device_drain.ready": 1.0,
            "device_drain.d2h": 0.15, "device_drain.host_copy": 0.04,
            "write_sink": 0.1,
        },
        intervals=[
            ("disk_read", 0.1, 0.5, "reader", 0.30),
            ("disk_read", 0.6, 1.1, "reader", 0.35),
            ("h2d_dispatch", 0.5, 0.65, "rpc", 0.10),  # inside the span's claim
            ("h2d_dispatch.put", 0.5, 0.6, "rpc", 0.08),
            ("device_drain", 0.5, 1.0, "sink", 0.02),
            ("device_drain.ready", 0.5, 0.9, "sink", 0.01),  # inside its parent
            ("device_drain", 1.2, 1.9, "sink", 0.03),
            ("write_sink", 1.9, 1.95, "sink", 0.01),
            ("queue_wait", 1.0, 1.1, "reader", -1),  # timed after the fact
        ],
    )
    root = span("rpc.ec_shards_rebuild", 0.0, 2.0, thread="rpc", cpu_s=0.45,
                children=[pipe])
    obs = harness.Observed()
    obs.spans = [root]
    obs.ops = [("op", 1, 10.0, 12.01, GIB), ("reset", 1, 12.01, 12.2, 0)]
    obs.bytes = GIB
    return obs


def test_the_parts_of_the_hand_off_per_gib():
    obs = rebuild_obs()
    assert reader("h2d_put_s_per_gib")(obs, None) == pytest.approx(0.20)
    assert reader("kernel_launch_s_per_gib")(obs, None) == pytest.approx(0.09)
    assert reader("drain_ready_s_per_gib")(obs, None) == pytest.approx(1.0)
    assert reader("d2h_copy_s_per_gib")(obs, None) == pytest.approx(0.19)
    # the parts stand beside the lumps the accepted readers read
    assert reader("h2d_dispatch_s_per_gib")(obs, None) == pytest.approx(0.30)
    assert reader("device_drain_s_per_gib")(obs, None) == pytest.approx(1.2)
    # two operations of half the bytes each: the same per GiB
    obs.spans = obs.spans * 2
    obs.ops = obs.ops * 2
    obs.bytes = 2 * GIB
    assert reader("drain_ready_s_per_gib")(obs, None) == pytest.approx(1.0)


def test_thread_shares_are_unions_over_the_operations_wall():
    obs = rebuild_obs()
    # disk_read covers 0.4 + 0.5 s of 2.0 s; device_drain 0.5 + 0.7 s
    assert reader("reader_busy_share")(obs, None) == pytest.approx(45.0)
    assert reader("sink_device_wait_share")(obs, None) == pytest.approx(60.0)
    # intervals that overlap count once: a second reader over 0.4-0.7
    obs.spans[0]["children"][0]["intervals"].append(
        ["disk_read", int(0.4 * S), int(0.7 * S), "reader2", 0]
    )
    assert reader("reader_busy_share")(obs, None) == pytest.approx(50.0)


def test_cpu_seconds_count_each_thread_once():
    obs = rebuild_obs()
    # rpc thread: the root's 0.45 (the child span and its stages on that
    # thread lie inside it); reader: 0.30 + 0.35; sink: 0.02 + 0.03 +
    # 0.01 (device_drain.ready lies inside its parent)
    assert spanlib.tree_cpu_seconds(obs.spans[0]) == pytest.approx(1.16)
    assert reader("pipeline_cpu_s_per_gib")(obs, None) == pytest.approx(1.16)


def get_obs(n=2):
    obs = harness.Observed()
    for i in range(n):
        read = span(
            "ec.degraded_read", 0.010, 0.110, thread="w", cpu_s=0.008,
            stages={"sibling_read": 0.030, "crc_verify": 0.020,
                    "admission_wait": 0.025, "reconstruct": 0.020,
                    "reconstruct.ready": 0.012},
        )
        obs.spans.append(span(
            "http.volume", 0.0, 0.150, thread="w", cpu_s=0.010 + 0.002 * i,
            stages={"ready_wait": 0.004 * i, "parse": 0.001, "volume.read": 0.105,
                    "send": 0.030},
            children=[read], attrs={"op_class": "read"},
        ))
    obs.spans.append(span("http.volume", 0.0, 9.0, attrs={"op_class": "write"}))
    obs.gets = [(0.0, 0.2)] * n
    return obs


def test_a_gets_work_and_waiting():
    obs = get_obs()
    assert reader("get_cpu_ms_per_get")(obs, None) == pytest.approx(11.0)
    assert reader("ready_wait_ms_per_get")(obs, None) == pytest.approx(2.0)
    # 150 ms of root, 100 ms under the child span
    assert reader("frontend_self_ms_per_get")(obs, None) == pytest.approx(50.0)
    assert reader("sibling_read_ms_per_get")(obs, None) == pytest.approx(30.0)
    assert reader("crc_verify_ms_per_get")(obs, None) == pytest.approx(20.0)
    assert reader("rs_apply_ms_per_get")(obs, None) == pytest.approx(20.0)
    # the accepted readers beside them read what they read
    assert reader("reconstruct_ms_per_get")(obs, None) == pytest.approx(100.0)
    assert reader("admission_wait_ms_per_get")(obs, None) == pytest.approx(25.0)
    # child spans that overlap are covered once; one beyond the root's
    # end covers only what lies inside
    root = obs.spans[0]
    root["children"].append(span("ec.degraded_read", 0.100, 0.200))
    assert spanlib.self_seconds(root) == pytest.approx(0.010)


def test_a_program_without_the_timeline_gives_nothing_to_read():
    """The parent of the PR that brought these readers: accumulators
    only. Nothing is returned, nothing raises; the stage readers read
    the stages that were always there."""
    obs = harness.Observed()
    pipe = parent_span("ec.rebuild", 1.9, stages={"disk_read": 1.0, "h2d_dispatch": 0.3,
                                                  "device_drain": 1.2})
    obs.spans = [parent_span("rpc.ec_shards_rebuild", 2.0, children=[pipe])]
    obs.ops = [("op", 1, 0.0, 2.0, GIB)]
    obs.bytes = GIB
    for name in ("h2d_put_s_per_gib", "kernel_launch_s_per_gib", "drain_ready_s_per_gib",
                 "d2h_copy_s_per_gib", "reader_busy_share", "sink_device_wait_share",
                 "pipeline_cpu_s_per_gib", "idle_unattributed_share"):
        assert reader(name)(obs, None) is None, name
    gets = harness.Observed()
    read = parent_span("ec.degraded_read", 0.1, stages={"sibling_read": 0.03})
    gets.spans = [parent_span("http.volume", 0.15, children=[read],
                              attrs={"op_class": "read"})]
    gets.gets = [(0.0, 0.2)]
    for name in ("get_cpu_ms_per_get", "ready_wait_ms_per_get", "frontend_self_ms_per_get",
                 "rs_glue_share_of_device", "get_idle_unattributed_share"):
        assert reader(name)(gets, None) is None, name
    assert reader("sibling_read_ms_per_get")(gets, None) == pytest.approx(30.0)
    assert reader("rs_apply_ms_per_get")(gets, None) == 0.0
    # no GET, no operation: nothing
    empty = harness.Observed()
    for m in harness.load_json(harness.ROOT / "BENCHMARK.json")["per_layer"][14:]:
        assert reader(m["name"])(empty, None) is None, m["name"]


def test_the_glue_is_every_device_second_outside_the_named_kernel():
    obs = harness.Observed()
    obs.device = {"busy_s": 0.05, "device_ops": [
        ["concatenate.1", 0.017], ["broadcast_in_dim.1", 0.015], ["sw_rs_apply.1", 0.010],
        ["sw_rs_apply.2", 0.003], ["reduce", 0.005],
    ]}
    assert reader("rs_glue_share_of_device")(obs, None) == pytest.approx(100 * 37 / 50)
    # the kernel under the name a refactor gave it is not the kernel
    obs.device["device_ops"] = [["apply_bitmajor_pallas.1", 0.013], ["copy.1", 0.002]]
    assert reader("rs_glue_share_of_device")(obs, None) is None
    obs.device["device_ops"] = [["sw_rs_apply.1", 0.013]]
    assert reader("rs_glue_share_of_device")(obs, None) == 0.0


def test_idle_time_is_laid_to_the_stages_open_by_hand():
    events = {
        "device": [["/device:TPU:0", "sw_rs_apply.1", 2.0, 1.0],
                   ["/device:TPU:0", "copy.1", 2.5, 1.0]],  # busy 2.0-3.5
        "host": [
            ["sw:ec.rebuild", 0, 0.0, 10.0],              # a span: attributes nothing
            ["ecbench.op.ec.rebuild", 0, 0.0, 10.0],
            ["sw:ec.rebuild/disk_read", 1, 1.0, 2.0],     # idle under it: 1.0-2.0
            ["sw:ec.rebuild/device_drain", 2, 1.5, 3.0],  # idle: 1.5-2.0, 3.5-4.5
            ["sw:ec.rebuild/device_drain.ready", 2, 1.5, 2.5],  # idle: 1.5-2.0, 3.5-4.0
            ["sw:ec.rebuild/write_sink", 2, 6.0, 1.0],    # idle: 6.0-7.0
        ],
    }
    found = spanlib.idle_attribution(events)
    assert found["idle_s"] == pytest.approx(8.5)  # 10 s less 1.5 s busy
    assert found["by_stage"] == pytest.approx({
        "ec.rebuild/device_drain": 1.5, "ec.rebuild/disk_read": 1.0,
        "ec.rebuild/write_sink": 1.0, "ec.rebuild/device_drain.ready": 1.0,
    })
    assert list(found["by_stage"])[0] == "ec.rebuild/device_drain"  # largest first
    # covered idle: 1.0-2.0, 3.5-4.5, 6.0-7.0; the rest has no stage open
    assert found["unattributed_s"] == pytest.approx(8.5 - 3.0)
    # no device operation, or a program that annotates nothing: nothing
    assert spanlib.idle_attribution({"device": [], "host": events["host"]}) is None
    only_drivers = [e for e in events["host"] if e[0].startswith("ecbench.")]
    assert spanlib.idle_attribution({"device": events["device"], "host": only_drivers}) is None


RECORDED = ("vol1g-10p4.rebuild", "vol1g-10p4.degraded-get")


@pytest.mark.parametrize("cell", RECORDED)
def test_the_recorded_trace_has_the_program_on_the_devices_clock(cell):
    """The device operations of the first 0.6 s of a traced slice of
    each cell on a v5e, and what the host had open meanwhile (recorded
    by record_sw_events.py beside this file, PR 26)."""
    events = json.loads((HERE / f"{cell}.sw_trace_events.json").read_text())
    assert any(n.split(".")[0] == spanlib.KERNEL_NAME for _p, n, _s, _d in events["device"])
    stages = {n for n, *_ in events["host"] if n.startswith("sw:") and "/" in n}
    want = (
        {"sw:ec.rebuild/disk_read", "sw:ec.rebuild/h2d_dispatch.launch",
         "sw:ec.rebuild/device_drain.d2h"}
        if cell.endswith("rebuild") else
        {"sw:ec.degraded_read/sibling_read", "sw:ec.degraded_read/reconstruct.ready"}
    )
    assert want <= stages
    found = spanlib.idle_attribution(events)
    lo = min(s for _n, _r, s, _d in events["host"])  # open when the sample begins
    hi = max(s + d for _n, _r, s, d in events["host"])
    assert 0 < found["idle_s"] <= hi - lo
    assert 0 <= found["unattributed_s"] <= found["idle_s"]
    assert all(0 <= s <= found["idle_s"] + 1e-9 for s in found["by_stage"].values())
    # one clock: the device's operations lie inside stages of the program
    # that were open on the host (all but the few per cent that
    # `idle_unattributed_share` reads)
    opened = [(s, s + d) for n, _r, s, d in events["host"] if n in stages]
    first = min(a for a, _b in opened)
    sample = [e for e in events["device"] if e[2] >= first][:100]
    inside = sum(any(a <= s and s + d <= b for a, b in opened) for _p, _n, s, d in sample)
    assert inside >= 0.9 * len(sample)


@pytest.mark.parametrize("cell", RECORDED)
def test_the_recorded_span_documents_hold_the_identities(cell):
    """Two root span documents of the same runs: the parts of a stage
    make up the stage, and intervals lie inside their spans."""
    docs = json.loads((HERE / "span_docs.json").read_text())[cell]
    assert docs
    for root in docs:
        for d in spanlib.walk(root):
            st = d["stages"]
            for parent in ("h2d_dispatch", "device_drain", "reconstruct"):
                parts = sum(a["seconds"] for s, a in st.items() if s.startswith(parent + "."))
                if parts:
                    assert 0.97 * st[parent]["seconds"] <= parts <= st[parent]["seconds"]
            for stage, t0, t1, _thread, _cpu in d["intervals"]:
                if stage != "ready_wait":  # ends where its span starts
                    assert d["start_ns"] <= t0 <= t1 <= d["end_ns"], stage
        cpu = spanlib.tree_cpu_seconds(root)
        assert 0 < cpu
        assert spanlib.self_seconds(root) <= root["duration_s"]
