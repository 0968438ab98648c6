"""`sibling_batched_share` on span documents worked by hand, and the
accepted readers, unchanged, on the span documents of reconstructions
recorded after the degraded read took its siblings as one matrix
(`matrix_get.span_docs.json`, from a traced v5e run of PR 27 through
record_sw_events.py beside this file)."""

import json
import pathlib

import pytest

from ecbench import harness, spanlib

HERE = pathlib.Path(__file__).resolve().parent
PARTS = ("reconstruct.put", "reconstruct.launch", "reconstruct.ready", "reconstruct.d2h")


def reader(name):
    return harness.load_module("layers", name).read


def get_root(*reads):
    """A GET's root span with these `ec.degraded_read` attributes."""
    children = [
        {"op": "ec.degraded_read", "duration_s": 0.05, "attrs": dict(a), "stages": {},
         "children": []}
        for a in reads
    ]
    return {"op": "http.volume", "duration_s": 0.1, "attrs": {"op_class": "read"},
            "stages": {}, "children": children}


def obs_of(*roots):
    obs = harness.Observed()
    obs.spans = list(roots)
    return obs


def test_the_share_is_batched_rows_over_all_rows_filled():
    share = reader("sibling_batched_share")
    # both counts: 10 + 9 batched, 1 refilled after a failed check
    obs = obs_of(
        get_root({"sibling_rows_batched": 10}),
        get_root({"sibling_rows_batched": 9, "sibling_rows_single": 1}),
        get_root(),  # a cache hit: no row filled
    )
    assert share(obs, None) == pytest.approx(95.0)
    # one count missing: all batched, or all one by one
    assert share(obs_of(get_root({"sibling_rows_batched": 10})), None) == 100.0
    assert share(obs_of(get_root({"sibling_rows_single": 10})), None) == 0.0
    # two reconstructions under one GET (a needle over two intervals)
    two = get_root({"sibling_rows_batched": 10}, {"sibling_rows_single": 10})
    assert share(obs_of(two), None) == pytest.approx(50.0)
    # a write's root span is no GET
    write = get_root({"sibling_rows_single": 10})
    write["attrs"]["op_class"] = "write"
    assert share(obs_of(get_root({"sibling_rows_batched": 10}), write), None) == 100.0


def test_a_program_that_counts_neither_gives_nothing_to_read():
    share = reader("sibling_batched_share")
    # the parent of the PR that brought the counts: bytes and batches only
    old = get_root({"h2d_bytes": 2621440, "batches": 1, "d2h_bytes": 262144})
    assert share(obs_of(old), None) is None
    assert share(obs_of(get_root()), None) is None
    assert share(harness.Observed(), None) is None


@pytest.fixture(scope="module")
def recorded():
    return json.loads((HERE / "matrix_get.span_docs.json").read_text())


def test_the_recorded_reconstructions_keep_their_stages_and_parts(recorded):
    """Each recorded GET reconstructed once: `sibling_read`, `crc_verify`
    and `reconstruct` are still entered, `reconstruct` is made up of
    `.put`, `.launch`, `.ready` and `.d2h`, and `.stack` is gone."""
    assert recorded
    for root in recorded:
        (read,) = [d for d in spanlib.walk(root) if d["op"] == "ec.degraded_read"]
        st = read["stages"]
        assert st["sibling_read"]["count"] == 1  # one batched read
        assert st["crc_verify"]["count"] == 2  # the matrix, then the output row
        assert st["reconstruct"]["count"] == 1 and st["admission_wait"]["count"] == 1
        assert all(st[p]["count"] == 1 for p in PARTS)
        assert "reconstruct.stack" not in st
        parts = sum(st[p]["seconds"] for p in PARTS)
        assert 0.97 * st["reconstruct"]["seconds"] <= parts <= st["reconstruct"]["seconds"]
        assert read["attrs"]["sibling_rows_batched"] == 10
        assert "sibling_rows_single" not in read["attrs"]
        # one put of the whole matrix: ten rows up, one row back
        assert read["attrs"]["h2d_bytes"] == 10 * read["attrs"]["d2h_bytes"]
        assert read["attrs"]["batches"] == 1
        for stage, t0, t1, _thread, _cpu in read["intervals"]:
            assert read["start_ns"] <= t0 <= t1 <= read["end_ns"], stage


def test_the_accepted_readers_read_the_recorded_documents_unchanged(recorded):
    obs = obs_of(*recorded)
    n = len(recorded)
    reads = [d for r in recorded for d in spanlib.walk(r) if d["op"] == "ec.degraded_read"]

    def per_get(stage):
        return 1e3 * sum(d["stages"][stage]["seconds"] for d in reads) / n

    for metric, stage in (
        ("sibling_read_ms_per_get", "sibling_read"), ("crc_verify_ms_per_get", "crc_verify"),
        ("rs_apply_ms_per_get", "reconstruct"), ("admission_wait_ms_per_get", "admission_wait"),
    ):
        assert reader(metric)(obs, None) == pytest.approx(per_get(stage)), metric
        assert reader(metric)(obs, None) > 0
    assert reader("reconstruct_ms_per_get")(obs, None) == pytest.approx(
        1e3 * sum(d["duration_s"] for d in reads) / n
    )
    assert reader("get_cpu_ms_per_get")(obs, None) == pytest.approx(
        1e3 * sum(r["cpu_s"] for r in recorded) / n
    )
    assert 0 < reader("frontend_self_ms_per_get")(obs, None) < 1e3 * max(
        r["duration_s"] for r in recorded
    )
    assert reader("ready_wait_ms_per_get")(obs, None) >= 0
    assert reader("sibling_batched_share")(obs, None) == 100.0
