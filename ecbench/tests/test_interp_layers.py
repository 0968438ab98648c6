"""The readers of the program's wait probes and seam stamps (PR 34):
each on span documents worked by hand, on documents recorded from traced
v5e runs with the probes (`probe.span_docs.json`) and without them (the
parents': `ycsb_get.span_docs.json`, `mixed.span_docs.json`, where every
reader finds nothing and says nothing), the in-pipeline cut on an
operation with two `disk_read` intervals, and the entries that
BENCHMARK.json gained."""

import io
import json
import pathlib

import pytest

from ecbench import harness, probelib

HERE = pathlib.Path(__file__).resolve().parent
S = 1_000_000_000  # ns
MS = 1_000_000
GIB = 1 << 30

GETS = [
    "vol1g-10p4.degraded-get", "vol1g-10p4-node-down.ycsb-c",
    "vol1g-x2-10p4-recovering.ycsb-c-under-rebuild",
    "vol1g-10p4-7vs-node-down.ycsb-c-spread",  # since ISSUE 36
]
MIXED = GETS[2:3]
# name -> (unit, layer, moves, cells), as ISSUE 34's table has them, the
# six read in GET cells with the spread cell that ISSUE 36 put on their lists
ENTRIES = {
    "interp_wait_ms": ("ms", "HTTP front end", "fg_p50_ms", GETS),
    "core_wait_ms": ("ms", "device", "fg_p50_ms", GETS),
    "interp_wait_ms_per_get": ("ms", "HTTP front end", "fg_p50_ms", GETS),
    "interp_returns_per_get": ("1", "HTTP front end", "fg_ops_per_s", GETS),
    "other_python_cpu_share": ("%", "HTTP front end", "fg_ops_per_s", GETS),
    "cores_busy": ("1", "device", "fg_ops_per_s", GETS),
    "interp_wait_in_pipeline_ms": ("ms", "EC pipeline", "fg_p95_ms", MIXED),
    "core_wait_in_pipeline_ms": ("ms", "EC pipeline", "fg_p95_ms", MIXED),
}


def reader(name):
    return harness.load_module("layers", name).read


def span(op, t0, t1, attrs=None, children=(), intervals=(), thread="w"):
    """A span document as `Span.to_dict()` writes it, times in seconds."""
    return {
        "op": op, "name": op, "start_ns": int(t0 * S), "end_ns": int(t1 * S),
        "duration_s": t1 - t0, "cpu_s": 0.0, "thread": thread,
        "attrs": attrs or {}, "events": [], "stages": {},
        "intervals": [[s, int(a * S), int(b * S), th, -1] for s, a, b, th in intervals],
        "children": list(children),
    }


def get_root(t0, t1, attrs=None, children=()):
    return span("http.volume", t0, t1, {"op_class": "read", **(attrs or {})}, children)


def probe(t0, t1, py, core, cpu_ms, process_ms):
    """An `interp.probe` span over [t0, t1) s: samples as (wake s, wait
    ms), CPU by class and the process's in ms."""
    attrs = {
        "period_ns": 5 * MS,
        "py_samples": [[int(t * S), int(w * MS)] for t, w in py],
        "cpu_ns": {cls: int(ms * MS) for cls, ms in cpu_ms.items()},
        "process_cpu_ns": int(process_ms * MS),
    }
    if core is not None:
        attrs["core_samples"] = [[int(t * S), int(w * MS)] for t, w in core]
    return span("interp.probe", t0, t1, attrs, thread="sw-interp-probe")


def worked():
    """Four GETs and two probe intervals. GET 1 is healthy (one return,
    2 ms), GET 2 reconstructs (one on the root, three on its degraded
    read: 1 + 2 + 3 + 4 = 10 ms), GETs 3 and 4 crossed no seam. A rebuild
    whose reader ran 10.02-10.05 and 10.12-10.16."""
    read = span("ec.degraded_read", 10.01, 10.04,
                {"interp_wait_ns": 9 * MS, "interp_returns": 3})
    gets = [
        get_root(10.00, 10.03, {"interp_wait_ns": 2 * MS, "interp_returns": 1}),
        get_root(10.00, 10.06, {"interp_wait_ns": 1 * MS, "interp_returns": 1}, [read]),
        get_root(10.10, 10.12), get_root(10.10, 10.13),
    ]
    pipe = span("ec.rebuild", 10.01, 10.18, intervals=[
        ("disk_read", 10.02, 10.05, "ec-pipe-reader"),
        ("disk_read", 10.12, 10.16, "ec-pipe-reader"),
        ("write_sink", 10.05, 10.17, "ec-pipe-sink"),
    ])
    op = span("rpc.ec_shards_rebuild", 10.0, 10.19, children=[pipe])
    probes = [
        probe(10.0, 10.1,
              py=[(10.01, 1.0), (10.03, 6.0), (10.04, 8.0), (10.07, 2.0), (10.09, 3.0)],
              core=[(10.01, 0.1), (10.03, 0.5), (10.04, 0.7), (10.07, 0.2)],
              cpu_ms={"http_workers": 60, "other_python": 50, "probe": 5, "native": 35},
              process_ms=150),
        probe(10.1, 10.2,
              py=[(10.11, 4.0), (10.13, 9.0), (10.15, 7.0), (10.19, 5.0)],
              core=[(10.11, 0.3), (10.13, 0.9), (10.15, 0.6), (10.19, 0.4)],
              cpu_ms={"http_workers": 70, "other_python": 40, "pipe_reader": 5, "native": 135},
              process_ms=250),
    ]
    obs = harness.Observed()
    obs.spans = gets + [op] + probes
    obs.ops = [("op", 2, 10.0, 10.19, GIB)]
    obs.bytes = GIB
    return obs


WORKED = {
    # nine Python samples, sorted 1 2 3 4 5 6 7 8 9: nearest rank 9 // 2
    "interp_wait_ms": 5.0,
    # eight native ones, sorted .1 .2 .3 .4 .5 .6 .7 .9: rank 8 // 2
    "core_wait_ms": 0.5,
    "interp_wait_ms_per_get": (2 + 1 + 9) / 4,
    "interp_returns_per_get": (1 + 1 + 3) / 4,
    "other_python_cpu_share": 100.0 * 90 / 400,
    "cores_busy": 0.400 / 0.2,
    # woke while the reader ran: 10.03, 10.04, 10.13, 10.15
    "interp_wait_in_pipeline_ms": 8.0,  # 6 7 8 9: rank 4 // 2
    "core_wait_in_pipeline_ms": 0.7,    # .5 .6 .7 .9
}


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_on_documents_worked_by_hand(name):
    assert reader(name)(worked(), None) == pytest.approx(WORKED[name])


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_program_without_the_probes_gives_nothing_to_read(name):
    obs = worked()
    obs.spans = [d for d in obs.spans if d["op"] != "interp.probe"]
    for root in obs.spans:
        for d in probelib.walk(root):
            d["attrs"].pop("interp_wait_ns", None)
            d["attrs"].pop("interp_returns", None)
    assert reader(name)(obs, None) is None
    assert reader(name)(harness.Observed(), None) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
@pytest.mark.parametrize("recorded", ["ycsb_get.span_docs.json", "mixed.span_docs.json"])
def test_the_recorded_parents_spans_leave_every_new_metric_out(name, recorded):
    """What the parent gives under this PR's benchmark files: nothing to
    read, and nothing raised."""
    docs = json.loads((HERE / recorded).read_text())
    obs = harness.Observed()
    if isinstance(docs, dict):
        obs.spans, obs.ops = docs["spans"], [tuple(o) for o in docs["ops"]]
    else:
        obs.spans = docs
    assert any(d["op"] == "http.volume" for d in obs.spans)
    assert reader(name)(obs, None) is None


def test_the_in_pipeline_cut_takes_the_samples_inside_two_disk_read_intervals():
    obs = worked()
    ivs = probelib.pipeline_intervals(obs)
    assert ivs == [pytest.approx((10.02, 10.05)), pytest.approx((10.12, 10.16))]
    py = probelib.samples(obs, "py")
    assert [w for _t, w in probelib.inside(py, ivs)] == [6.0, 8.0, 9.0, 7.0]
    # a sample on an interval's first instant is inside, on its last is not
    edge = [(10.02, 1.0), (10.05, 2.0), (10.12, 3.0), (10.16, 4.0), (10.30, 5.0)]
    assert [w for _t, w in probelib.inside(edge, ivs)] == [1.0, 3.0]
    # overlapping intervals of two operations are one
    second = span("rpc.ec_shards_rebuild", 10.0, 10.2, children=[
        span("ec.rebuild", 10.0, 10.2, intervals=[("disk_read", 10.04, 10.13, "r")])
    ])
    obs.spans.append(second)
    obs.ops.append(("op", 3, 10.0, 10.2, GIB))
    assert probelib.pipeline_intervals(obs) == [pytest.approx((10.02, 10.16))]


def test_the_warm_ups_rebuild_is_not_the_windows_pipeline():
    obs = worked()
    warm = span("rpc.ec_shards_rebuild", 1.0, 2.0, children=[
        span("ec.rebuild", 1.0, 2.0, intervals=[("disk_read", 1.0, 2.0, "r")])
    ])
    obs.spans.insert(0, warm)  # first in the ring, and no `ops` entry
    assert probelib.pipeline_intervals(obs)[0][0] == pytest.approx(10.02)


@pytest.mark.parametrize("name", ["interp_wait_in_pipeline_ms", "core_wait_in_pipeline_ms"])
def test_a_window_with_no_rebuild_has_no_pipeline_to_cut_by(name):
    obs = worked()
    obs.spans = [d for d in obs.spans if d["op"] != "rpc.ec_shards_rebuild"]
    obs.ops = []
    assert reader(name)(obs, None) is None
    assert reader("interp_wait_ms")(obs, None) == pytest.approx(5.0)


def test_a_host_without_the_native_library_reports_the_python_probe_alone():
    obs = worked()
    for d in obs.spans:
        d["attrs"].pop("core_samples", None)
    assert reader("core_wait_ms")(obs, None) is None
    assert reader("core_wait_in_pipeline_ms")(obs, None) is None
    assert reader("interp_wait_ms")(obs, None) == pytest.approx(5.0)
    assert reader("interp_wait_in_pipeline_ms")(obs, None) == pytest.approx(8.0)


def test_the_tables_say_what_the_metrics_are_taken_from():
    out = io.StringIO()
    probelib.describe(worked(), out)
    said = out.getvalue()
    assert "0.400 s over 0.200 s of probe spans = 2.000 cores busy" in said
    assert "other_python=0.090(22.5%,39.1%)" in said  # 90 of the 230 ms Python threads read
    assert "native=0.170(42.5%,-)" in said
    assert "while a rebuild's reader ran (0.07 s), n=4 p50=8.000" in said
    assert "between the rebuilds' spans" in said
    assert "ec.degraded_read: 3 returns, 3.000 ms each, 2.250 ms a root;" in said
    assert "http.volume: 2 returns, 1.500 ms each, 0.750 ms a root;" in said
    quiet = io.StringIO()
    probelib.describe(harness.Observed(), quiet)
    assert quiet.getvalue() == ""


# ------------------------------------- recorded with the probes, on a v5e


@pytest.fixture(scope="module")
def recorded():
    doc = json.loads((HERE / "probe.span_docs.json").read_text())
    obs = harness.Observed()
    obs.spans = doc["spans"]
    obs.ops = [tuple(o) for o in doc["ops"]]
    return doc, obs


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_reader_on_documents_recorded_with_the_probes(recorded, name):
    doc, obs = recorded
    assert reader(name)(obs, None) == pytest.approx(doc["by_hand"][name])


def test_the_recorded_probe_spans_are_what_the_program_promises(recorded):
    doc, obs = recorded
    probes = probelib.probe_spans(obs)
    assert len(probes) >= 3 and "v5 lite" in doc["recorded"]
    for d in probes:
        attrs = d["attrs"]
        assert d["end_ns"] - d["start_ns"] >= 100 * MS
        for summary, samples in (("py_wait_ns", "py_samples"), ("core_wait_ns", "core_samples")):
            waits = sorted(w for _t, w in attrs[samples])
            assert attrs[summary]["count"] == len(waits) > 0
            assert attrs[summary]["p50"] == waits[len(waits) // 2]
            assert attrs[summary]["max"] == waits[-1]
        assert {"http_workers", "other_python", "native", "probe"} <= set(attrs["cpu_ns"])
        assert sum(attrs["cpu_ns"].values()) >= attrs["process_cpu_ns"] - 50 * MS
    # the native probe is never later than the Python one by much: it
    # waits for a core, its twin for a core and the interpreter
    assert reader("core_wait_ms")(obs, None) < reader("interp_wait_ms")(obs, None)
    stamped = [
        d["attrs"] for r in probelib.get_roots(obs) for d in probelib.walk(r)
        if "interp_returns" in d["attrs"]
    ]
    assert stamped and all(a["interp_wait_ns"] > 0 for a in stamped)


# ------------------------------------------------------------ the manifest


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_the_appended_entry_is_the_issues(manifest, name):
    unit, layer, moves, cells = ENTRIES[name]
    (m,) = [m for m in manifest["per_layer"] if m["name"] == name]
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert (m["unit"], m["better"], m["layer"], m["moves"]) == (unit, "lower", layer, moves)
    assert m["workloads"] == cells
    assert m["source"] in ("program_span", "program_counter")
    # a layer that BENCHMARK.json already named, letter for letter
    older = {x["layer"] for x in manifest["per_layer"] if x["name"] not in ENTRIES}
    assert layer in older
    reported = {
        e["name"]: harness.metric_cells(e, manifest) for e in manifest["end_to_end"]
    }
    assert set(cells) <= reported[moves]


def test_the_new_entries_stand_together_in_the_issues_order(manifest):
    """After PR 33's last and before whatever a later PR appended (PR
    36: the five readers of `peer shard reads`)."""
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index("mixed_rs_roofline") + 1  # PR 33's last
    assert names[first:first + len(ENTRIES)] == list(ENTRIES)
    assert not set(names[:first]) & set(ENTRIES)
    assert not any("rebuild" in c.split(".")[-1] and "ycsb" not in c
                   for name in ENTRIES for c in ENTRIES[name][3])


def test_a_traced_rehearsal_reports_the_new_metrics_with_numbers(manifest):
    """The program's own probes under the harness, on the CPU at 8 MiB:
    every metric the mixed cell lists comes with a number."""
    result = harness.run_cell(
        manifest, MIXED[0], 2**31 + 43, 3.0, True, require_tpu=False,
        overrides={"volume_bytes": 8 << 20, "ec_interval_cache_mb": 1},
        out=io.StringIO(),
    )
    assert result["correct"] is True and result["failed"] == 0
    for name in ENTRIES:
        value = result["metrics"][name]["value"]
        assert isinstance(value, float) and value >= 0, name
    assert result["metrics"]["cores_busy"]["value"] > 0.1
    assert 1.0 <= result["metrics"]["interp_returns_per_get"]["value"] <= 4.0
    from seaweedfs_tpu.utils import interp_probe, trace

    trace.configure(enabled=False)
    assert not interp_probe.running()
