"""`read_reused_share` on span documents worked by hand: the bytes that
the readers landed in matrices the pool had held over all the bytes they
landed, on the window's operations; nothing where the program counts no
bytes read."""

import json
import pathlib

import pytest

from ecbench import harness
from ecbench.layerlib import walk

GIB = 1 << 30
MIB = 1 << 20


def read(obs):
    return harness.load_module("layers", "read_reused_share").read(obs, None)


def op_root(attrs):
    pipe = {"op": "ec.rebuild", "attrs": attrs, "stages": {}, "children": []}
    return {"op": "rpc.ec_shards_rebuild", "attrs": {"volume": 1}, "stages": {},
            "duration_s": 2.0, "children": [pipe]}


def observed(*roots):
    obs = harness.Observed()
    obs.spans = list(roots)
    obs.ops = [("op", 1, 2.0 * i, 2.0 * i + 2.0, GIB) for i in range(len(roots))]
    obs.bytes = GIB * len(roots)
    return obs


def test_every_batch_in_a_held_matrix_is_a_hundred_per_cent():
    obs = observed(op_root({"read_bytes": 1040 * MIB, "read_reused_bytes": 1040 * MIB}))
    assert read(obs) == pytest.approx(100.0)


def test_the_share_is_taken_over_all_the_windows_operations():
    # one operation all in held matrices, one whose first batch was fresh
    obs = observed(
        op_root({"read_bytes": 1040 * MIB, "read_reused_bytes": 1040 * MIB}),
        op_root({"read_bytes": 1040 * MIB, "read_reused_bytes": 880 * MIB}),
    )
    assert read(obs) == pytest.approx(100.0 * 1920 / 2080)


def test_bytes_read_and_none_reused_is_nought_not_nothing():
    # the Python plane counts what it reads and takes nothing from the pool
    assert read(observed(op_root({"read_bytes": 1040 * MIB}))) == 0.0


def test_the_warm_ups_operation_is_not_the_windows():
    warm = op_root({"read_bytes": 1040 * MIB})  # fresh matrices, every one
    obs = observed(op_root({"read_bytes": 1040 * MIB, "read_reused_bytes": 1040 * MIB}))
    obs.spans.insert(0, warm)  # first in the ring, and no `ops` entry
    assert read(obs) == pytest.approx(100.0)


@pytest.mark.parametrize("attrs", [
    pytest.param({"d2h_bytes": 208 * MIB, "h2d_bytes": GIB}, id="no_read_counter"),
    pytest.param({}, id="no_counter_at_all"),
    pytest.param({"read_bytes": 0, "read_reused_bytes": 0}, id="nothing_read"),
])
def test_a_program_without_the_counter_gives_nothing_to_read(attrs):
    assert read(observed(op_root(attrs))) is None


def test_no_operation_nothing_to_read():
    assert read(harness.Observed()) is None


def test_the_recorded_parents_spans_leave_the_metric_out():
    """The rebuild cell's span documents as a chip run recorded them
    before the program counted its reads (what the parent gives under
    this PR's benchmark files): nothing to read, and nothing raised."""
    roots = json.loads(
        (pathlib.Path(__file__).parent / "span_docs.json").read_text()
    )["vol1g-10p4.rebuild"]
    assert roots and any("d2h_bytes" in d["attrs"] for r in roots for d in walk(r))
    assert read(observed(*roots)) is None

