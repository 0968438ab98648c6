"""`d2h_dense_share` on span documents worked by hand: the bytes that
came home as dense words over all the bytes fetched, on the window's
operations; nothing where the program counts no dense bytes."""

import pytest

from ecbench import harness

GIB = 1 << 30
MIB = 1 << 20


def read(obs):
    return harness.load_module("layers", "d2h_dense_share").read(obs, None)


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


def test_every_byte_home_as_words_is_a_hundred_per_cent():
    obs = observed(op_root({"d2h_bytes": 208 * MIB, "d2h_dense_bytes": 208 * MIB}))
    assert read(obs) == pytest.approx(100.0)


def test_the_share_is_taken_over_all_the_windows_operations():
    # one operation all dense, one that fetched 48 of its 208 MiB as bytes
    obs = observed(
        op_root({"d2h_bytes": 208 * MIB, "d2h_dense_bytes": 208 * MIB}),
        op_root({"d2h_bytes": 208 * MIB, "d2h_dense_bytes": 160 * MIB}),
    )
    assert read(obs) == pytest.approx(100.0 * 368 / 416)


def test_the_warm_ups_operation_is_not_the_windows():
    warm = op_root({"d2h_bytes": 208 * MIB})
    obs = observed(op_root({"d2h_bytes": 208 * MIB, "d2h_dense_bytes": 208 * MIB}))
    obs.spans.insert(0, warm)  # first in the ring, and no `ops` entry
    assert read(obs) == pytest.approx(100.0)


@pytest.mark.parametrize("attrs", [
    pytest.param({"d2h_bytes": 208 * MIB, "h2d_bytes": GIB}, id="no_dense_counter"),
    pytest.param({}, id="no_counter_at_all"),
    pytest.param({"d2h_bytes": 0, "d2h_dense_bytes": 0}, id="nothing_fetched"),
])
def test_a_program_without_the_counter_gives_nothing_to_read(attrs):
    assert read(observed(op_root(attrs))) is None


def test_no_operation_nothing_to_read():
    assert read(harness.Observed()) is None
