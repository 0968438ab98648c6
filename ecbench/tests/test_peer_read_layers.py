"""The seven readers of one read from a peer (ISSUE 38; layer `peer
shard reads`, the spread cell alone): their entries in BENCHMARK.json,
and what they read from hand-worked documents, from documents kept from
a run, and from those of a program that opens no `ec.peer_read` span.

`peer_read.span_docs.json` beside this file: three GETs of the cluster
of tests/test_ec_spread_reads.py (8 MiB, CPU; seven servers, server 1
dead), armed: a needle with one interval on a peer, through server 2; a
needle on lost shard 1 through server 4, one interval from a peer and a
reconstruction from that server's own two rows and eight of ten fetches,
while server 6's shard plane took 0.3 s to look a shard up, so that its
two answers were not waited for (`unused`); the same GET again, from that
server's interval cache. With the holders' thirteen roots. Numbers on
the CPU sandbox say nothing about the chip: the documents are kept for
their SHAPE.
"""

import json
import pathlib
import types

import pytest

from ecbench import harness

HERE = pathlib.Path(__file__).resolve().parent
CELL = "vol1g-10p4-7vs-node-down.ycsb-c-spread"
# in the issue's order, with what each moves
SEVEN = {
    "peer_request_ms_per_read": ("ms", "lower", "program_span", "fg_p50_ms"),
    "peer_land_ms_per_read": ("ms", "lower", "program_span", "fg_p50_ms"),
    "peer_fetch_queue_ms_per_read": ("ms", "lower", "program_span", "fg_p95_ms"),
    "peer_wire_wait_ms_per_read": ("ms", "lower", "program_span", "fg_p50_ms"),
    "peer_serve_sendfile_share": ("%", "higher", "program_span", "fg_p50_ms"),
    "peer_interp_wait_ms_per_read": ("ms", "lower", "program_span", "fg_p50_ms"),
    "matrix_regather_share": ("%", "lower", "program_counter", "fg_ops_per_s"),
}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT / "BENCHMARK.json")


def reader(name):
    return harness.load_module("layers", name).read


def obs_of(docs):
    return types.SimpleNamespace(spans=docs)


def walk(doc):
    yield doc
    for c in doc["children"]:
        yield from walk(c)


# ------------------------------------------------------------ the entries


def test_the_seven_are_the_layers_last_entries_and_list_the_spread_cell_alone(manifest):
    entries = manifest["per_layer"][-len(SEVEN):]
    assert [m["name"] for m in entries] == list(SEVEN)
    reported = {e["name"]: harness.metric_cells(e, manifest) for e in manifest["end_to_end"]}
    for m in entries:
        unit, better, source, moves = SEVEN[m["name"]]
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (unit, better, source, moves)
        assert m["layer"] == "peer shard reads" and m["workloads"] == [CELL]
        assert CELL in reported[moves]
        assert callable(reader(m["name"]))
    # the layer's older five stand where they stood, unchanged
    older = [m["name"] for m in manifest["per_layer"] if m["layer"] == "peer shard reads"][:5]
    assert older == ["peer_read_ms_per_get", "peer_reads_per_get", "peer_serve_ms_per_read",
                     "remote_sibling_share", "peer_fetch_unused_share"]
    assert len(manifest["workloads"]) == 5 and len(manifest["configs"]) == 4


# ------------------------------------------------- documents worked by hand


def span(op, span_id="", parent="", duration_s=0.0, stages=None, attrs=None, children=()):
    return {
        "op": op, "name": op, "span_id": span_id, "parent_span_id": parent,
        "duration_s": duration_s, "events": [], "attrs": dict(attrs or {}),
        "stages": {s: {"seconds": t, "count": 1} for s, t in (stages or {}).items()},
        "children": list(children),
    }


def peer_read(span_id, kind, duration_s, stages, **attrs):
    return span("ec.peer_read", span_id, "p", duration_s, stages,
                {"kind": kind, "answered": 1, "plane": "native", **attrs})


def holder(parent, duration_s, sendfile, **attrs):
    return span("rpc.ec_shard_read", "h" + parent, parent, duration_s,
                {"stream": duration_s, "stream.resolve": 0.001, "stream.header": 0.002,
                 "stream.sendfile": sendfile}, attrs)


def hand_worked():
    """Two GETs. The first read one interval from a peer (50 ms: 1 of
    check-out, 30 of request, 19 of landing; its holder's span 20 ms, 5
    of them `sendfile`). The second reconstructed: of four fetches, two
    answered and were used (60 and 80 ms, 10 and 20 of them in the
    queue; the second asked two holders), one was closed under its
    thread (`unused`), one was answered by nobody; its matrix was
    gathered once more. A third reconstruction (in the first GET) asked
    peers and kept its matrix, a fourth found its extent cached."""
    a = peer_read("a", "interval", 0.050,
                  {"conn_checkout": 0.001, "request_rtt": 0.030, "payload_land": 0.019},
                  interp_wait_ns=4_000_000, interp_returns=1)
    b = peer_read("b", "sibling", 0.060,
                  {"fetch_queue": 0.010, "conn_checkout": 0.002, "request_rtt": 0.040,
                   "payload_land": 0.008}, interp_wait_ns=2_000_000)
    c = peer_read("c", "sibling", 0.080,
                  {"fetch_queue": 0.020, "conn_checkout": 0.0, "request_rtt": 0.050,
                   "payload_land": 0.010}, plane="stream")
    cut = peer_read("d", "sibling", 0.5, {"fetch_queue": 0.4, "conn_checkout": 0.1},
                    answered=0, unused=1, interp_wait_ns=9_000_000_000)
    nobody = peer_read("e", "sibling", 0.3, {"fetch_queue": 0.1, "request_rtt": 0.2}, answered=0)
    gathered = span("ec.degraded_read", "p", duration_s=0.2, children=[b, c, cut, nobody],
                    attrs={"peer_fetches_started": 4, "matrix_regathers": 1})
    kept = span("ec.degraded_read", attrs={"peer_fetches_started": 10, "matrix_regathers": 0})
    cached = span("ec.degraded_read")
    root = lambda children: span("http.volume", "p", children=children, attrs={"op_class": "read"})
    return [
        root([a, kept]), root([gathered, cached]),
        holder("a", 0.020, 0.005, interp_wait_ns=3_000_000),
        holder("b", 0.010, 0.004, interp_wait_ns=1_000_000),
        # the reader of `c` asked the plane, was refused, and asked the stream
        span("rpc.ec_shard_read", "hc1", "c", 0.004, {"stream": 0.004}, {"plane": "native"}),
        holder("c", 0.026, 0.006, interp_wait_ns=500_000),
        holder("d", 0.6, 0.001, interp_wait_ns=7_000_000_000),  # the unread answer's holder
        span("rpc.ec_shard_read", "orphan", "", 0.040, {"stream": 0.040}),  # a rebuild's stream
    ]


def test_the_seven_on_documents_worked_by_hand():
    obs = obs_of(hand_worked())
    # the three answered reads, the unread and the unanswered left out
    assert reader("peer_request_ms_per_read")(obs, None) == pytest.approx((30 + 40 + 50) / 3)
    assert reader("peer_land_ms_per_read")(obs, None) == pytest.approx((19 + 8 + 10) / 3)
    # over the two sibling reads alone
    assert reader("peer_fetch_queue_ms_per_read")(obs, None) == pytest.approx((10 + 20) / 2)
    # a: 50 - 0 - 20; b: 60 - 10 - 10; c: 80 - 20 - (4 + 26)
    assert reader("peer_wire_wait_ms_per_read")(obs, None) == pytest.approx((30 + 40 + 30) / 3)
    # every holder's root counts, joined or not: 16 ms of 700
    assert reader("peer_serve_sendfile_share")(obs, None) == pytest.approx(
        100 * (5 + 4 + 6 + 1) / (20 + 10 + 4 + 26 + 600 + 40)
    )
    # a: 4 + 3; b: 2 + 1; c: 0 + (0 + 0.5)
    assert reader("peer_interp_wait_ms_per_read")(obs, None) == pytest.approx(10.5 / 3)
    # of two reconstructions that asked peers, one gathered once more
    assert reader("matrix_regather_share")(obs, None) == pytest.approx(50.0)


def test_a_read_that_finds_no_holders_span_is_in_every_mean_but_the_joined_one():
    docs = hand_worked()
    obs = obs_of([d for d in docs if d.get("parent_span_id") != "b"])
    assert reader("peer_request_ms_per_read")(obs, None) == pytest.approx(40.0)
    assert reader("peer_wire_wait_ms_per_read")(obs, None) == pytest.approx((30 + 30) / 2)
    assert reader("peer_interp_wait_ms_per_read")(obs, None) == pytest.approx(9.5 / 3)
    shared = harness.load_module("layers", "peer_request_ms_per_read")
    assert len(shared.reads(obs)) == 3 and len(shared.joined(obs)) == 2
    assert len(shared.reads(obs, "sibling")) == 2 and len(shared.reads(obs, "interval")) == 1


def test_a_window_with_no_such_span_gives_each_of_them_nothing_and_none_raises():
    parents = [
        span("http.volume", attrs={"op_class": "read"}, stages={"peer_read": 0.25}, children=[
            span("ec.degraded_read", attrs={"peer_fetches_started": 10, "peer_fetches_unused": 2}),
        ]),
        span("rpc.ec_shard_read", "h", "root", 0.05, {"stream": 0.05}),
    ]
    for docs in (parents, []):
        for name in SEVEN:
            assert reader(name)(obs_of(docs), None) is None, name


def test_prs_35s_kept_documents_give_the_seven_nothing():
    docs = json.loads((HERE / "spread.span_docs.json").read_text())
    assert any(d["op"] == "rpc.ec_shard_read" for d in docs)
    for name in SEVEN:
        assert reader(name)(obs_of(docs), None) is None, name


# --------------------------------------------- documents kept from a run


@pytest.fixture(scope="module")
def kept():
    return json.loads((HERE / "peer_read.span_docs.json").read_text())


def test_the_kept_documents_are_the_three_gets_the_docstring_names(kept):
    gets = [d for d in kept if d["op"] == "http.volume"]
    served = [d for d in kept if d["op"] == "rpc.ec_shard_read"]
    assert len(gets) == 3 and len(served) == 13
    assert len({g["attrs"]["addr"] for g in gets}) == 2  # two entry servers
    reads = [d for g in gets for d in walk(g) if d["op"] == "ec.peer_read"]
    assert [r["attrs"]["kind"] for r in reads].count("interval") == 3
    assert [r["attrs"]["kind"] for r in reads].count("sibling") == 10
    unused = [r for r in reads if r["attrs"].get("unused")]
    assert len(unused) == 2 and all(r["attrs"]["answered"] == 0 for r in unused)
    (recon,) = [d for g in gets for d in walk(g)
                if d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"]]
    a = recon["attrs"]
    assert (a["peer_fetches_started"], a["peer_reads_outlived"], a["matrix_regathers"]) == (10, 2, 1)
    assert all(r["end_ns"] == recon["end_ns"] for r in unused)
    # every read, unread ones too, is named as parent by exactly one root
    parents = [d["parent_span_id"] for d in served]
    assert sorted(parents) == sorted(r["span_id"] for r in reads)
    for r in reads:
        if r["attrs"]["answered"]:
            total = sum(acc["seconds"] for acc in r["stages"].values())
            assert total == pytest.approx(r["duration_s"], rel=1e-6)


def test_the_seven_on_the_kept_documents(kept):
    obs = obs_of(kept)
    gets = [d for d in kept if d["op"] == "http.volume"]
    served = {d["parent_span_id"]: d for d in kept if d["op"] == "rpc.ec_shard_read"}
    reads = [d for g in gets for d in walk(g)
             if d["op"] == "ec.peer_read" and d["attrs"]["answered"] == 1]
    assert len(reads) == 11
    sec = lambda r, stage: r["stages"].get(stage, {}).get("seconds", 0.0)
    assert reader("peer_request_ms_per_read")(obs, None) == pytest.approx(
        1e3 * sum(sec(r, "request_rtt") for r in reads) / 11
    )
    assert reader("peer_land_ms_per_read")(obs, None) == pytest.approx(
        1e3 * sum(sec(r, "payload_land") for r in reads) / 11
    )
    rows = [r for r in reads if r["attrs"]["kind"] == "sibling"]
    assert len(rows) == 8
    assert reader("peer_fetch_queue_ms_per_read")(obs, None) == pytest.approx(
        1e3 * sum(sec(r, "fetch_queue") for r in rows) / 8
    )
    assert reader("peer_wire_wait_ms_per_read")(obs, None) == pytest.approx(
        1e3 * sum(r["duration_s"] - sec(r, "fetch_queue") - served[r["span_id"]]["duration_s"]
                  for r in reads) / 11
    )
    # all thirteen holders' roots, the two that slept 0.3 s in `.resolve` too
    assert reader("peer_serve_sendfile_share")(obs, None) == pytest.approx(
        100 * sum(sec(h, "stream.sendfile") for h in served.values())
        / sum(h["duration_s"] for h in served.values())
    )
    assert reader("peer_serve_sendfile_share")(obs, None) < 1.0  # 600 ms of look-up
    assert reader("peer_interp_wait_ms_per_read")(obs, None) == pytest.approx(
        sum(r["attrs"]["interp_wait_ns"] + served[r["span_id"]]["attrs"]["interp_wait_ns"]
            for r in reads) / 1e6 / 11
    )
    # one reconstruction asked peers, and gathered; the cached one asked nobody
    assert reader("matrix_regather_share")(obs, None) == 100.0


def test_the_kept_documents_give_the_same_values_through_metrics(manifest, kept):
    """What a traced run's result line carries: the harness's walk over
    the entries that list the cell finds the seven readers' files."""
    obs = harness.Observed()
    obs.spans = kept
    obs.counters["compiles_in_window"] = 0
    metrics = harness.read_layers(harness.resolve_cell(manifest, CELL, 1, 20.0, True), obs)
    for name, (unit, *_rest) in SEVEN.items():
        assert metrics[name] == {
            "value": pytest.approx(reader(name)(obs_of(kept), None)), "unit": unit,
        }, name
    # the layer's older five read the same documents as they always did
    assert metrics["peer_reads_per_get"]["value"] == pytest.approx(13 / 3)
    assert metrics["peer_fetch_unused_share"]["value"] == pytest.approx(20.0)
    assert metrics["remote_sibling_share"]["value"] == pytest.approx(80.0)
    served = [d["duration_s"] for d in kept if d["op"] == "rpc.ec_shard_read"]
    assert metrics["peer_serve_ms_per_read"]["value"] == pytest.approx(1e3 * sum(served) / 13)
    # and the two stamp metrics now see the readers' `sn_recv_into` returns
    # under the GET roots: 3 on the roots, 3 on the reconstruction, 11 on reads
    assert metrics["interp_returns_per_get"]["value"] == pytest.approx(17 / 3)
