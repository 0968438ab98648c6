"""The yardstick's arithmetic on cases worked by hand: the roofline,
the trace reducer, the traffic generators, the spread."""

import itertools
import json
import pathlib

import numpy as np
import pytest

from ecbench import data, harness, layerlib, roofline, tracered
from ecbench.sweep import spread

HERE = pathlib.Path(__file__).resolve().parent
V5E = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
PLAN = {
    "large_body_bytes": 1 << 20, "small_per_gib": 300,
    "small_min_bytes": 1024, "small_max_bytes": 65536, "layout_seed": 24,
}


def test_roofline_of_a_1gib_encode_by_hand():
    # 1 GiB in, 4/10 of it out: 1.4 x 1073741824 = 1.503e9 bytes
    assert roofline.rs_bytes(1 << 30, 10, 4) == pytest.approx(1.5032e9, rel=1e-4)
    least, bound_by = roofline.least_seconds(1 << 30, 10, 4, V5E)
    assert least == pytest.approx(1.8355e-3, rel=1e-3)  # 1.84 ms at 819 GB/s
    assert bound_by == "bytes"
    # 512 int8 operations per data byte: 767 GB/s of data at 393 TOP/s
    assert 393e12 / (roofline.rs_ops(1, 10, 4)) == pytest.approx(767.6e9, rel=1e-3)


def test_roofline_reader_divides_by_busy_time_not_by_a_kernel():
    obs = harness.Observed()
    obs.ops = [("op", 1, 0.0, 2.0, 1 << 30), ("reset", 1, 2.0, 2.2, 0)]
    obs.slice_t = (1.0, 3.0)  # half of the operation lies inside
    obs.device = {"busy_s": 0.01}
    cell = type("C", (), {})()
    cell.peaks, cell.device_kind = {"TPU v5 lite": V5E}, "TPU v5 lite"
    cell.config = {"layout": {"data_shards": 10, "parity_shards": 4}}
    cell.traffic = {"op": "ec.encode"}
    assert layerlib.bytes_in_slice(obs) == pytest.approx((1 << 30) / 2)
    share = harness.load_module("layers", "rs_roofline").read(obs, cell)
    assert share == pytest.approx(100 * (1.8355e-3 / 2) / 0.01, rel=1e-3)
    per_gib = harness.load_module("layers", "rs_device_s_per_gib").read(obs, cell)
    assert per_gib == pytest.approx(0.02)
    # a rebuild of two shards writes 2 rows for the 10 it reads, not 4
    cell.traffic = {"op": "ec.rebuild", "lost_shards": [3, 11]}
    rebuilt = harness.load_module("layers", "rs_roofline").read(obs, cell)
    assert rebuilt == pytest.approx(share * 12 / 14)
    # nothing to read: nothing returned, never a 0
    obs.device = {"busy_s": 0.0}
    assert harness.load_module("layers", "rs_roofline").read(obs, cell) is None
    cell.device_kind = "some other chip"
    obs.device = {"busy_s": 0.01}
    assert harness.load_module("layers", "rs_roofline").read(obs, cell) is None


def test_trace_reducer_by_hand():
    events = {
        "device": [
            ["/device:TPU:0", "kernel", 1.0, 0.5],
            ["/device:TPU:0", "concatenate", 1.25, 0.5],  # overlaps the kernel
            ["/device:TPU:0", "kernel", 3.0, 1.0],
        ],
        "host": [
            ["ecbench.op.ec.encode", 0.0, 2.5],
            ["ecbench.reset", 2.5, 0.25],
            ["ecbench.op.ec.encode", 2.75, 2.25],
        ],
    }
    out = tracered.reduce_events(events)
    assert out["busy_s"] == pytest.approx(1.75)  # the union, not the sum 2.0
    assert out["device_ops"] == [["kernel", 1.5], ["concatenate", 0.5]]
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps["in_ec.encode"] == pytest.approx(3.0)
    assert gaps["in_the_reset_between_operations"] == pytest.approx(0.25)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(5.0)


def test_trace_reducer_averages_over_chips():
    events = {
        "device": [["/device:TPU:0", "k", 0.0, 1.0], ["/device:TPU:1", "k", 0.5, 2.0]],
        "host": [],
    }
    out = tracered.reduce_events(events)
    assert out["devices"] == 2 and out["busy_s"] == pytest.approx(1.5)
    assert tracered.reduce_events({"device": [], "host": []})["busy_s"] == 0.0


def test_trace_reducer_on_the_recorded_trace():
    """The first events of a traced run of vol1g-10p4.encode on a v5e
    (recorded by record_trace_events.py beside this file, PR 24)."""
    events = json.loads((HERE / "trace_events.json").read_text())
    out = tracered.reduce_events(events)
    total = sum(d for _p, _n, _s, d in events["device"])
    assert 0 < out["busy_s"] <= total + 1e-12
    assert out["devices"] == 1
    assert sum(s for _n, s in out["device_ops"]) == pytest.approx(total)
    lo = min(s for _p, _n, s, _d in events["device"])
    hi = max(s + d for _p, _n, s, d in events["device"])
    assert out["busy_s"] <= hi - lo
    assert any(name.startswith("/device:TPU:") for name, _lines in events["planes"])


def test_interval_arithmetic():
    a = tracered.union([(0, 2), (1, 3), (5, 6), (6, 6)])
    assert a == [(0, 3), (5, 6)]
    assert tracered.subtract([(0, 10)], a) == [(3, 5), (6, 10)]
    assert tracered.intersect(a, [(2, 5.5)]) == [(2, 3), (5, 5.5)]
    assert tracered.length(a) == 4


def test_needle_plan_is_the_configurations_and_the_seed_fills_it(tmp_path):
    one = data.needle_plan(1, 64 << 20, PLAN)
    assert one == data.needle_plan(1, 64 << 20, PLAN)
    other = data.needle_plan(1, 64 << 20, {**PLAN, "layout_seed": 25})
    assert other != one and sorted(other) == sorted(one)
    assert data.needle_plan(2, 64 << 20, PLAN) != one
    assert one.count(1 << 20) == 64
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    for d in "abc":
        (tmp_path / d).mkdir()
    a = data.fabricate_volume(str(tmp_path / "a"), 1, big, 4 << 20, PLAN)
    b = data.fabricate_volume(str(tmp_path / "b"), 1, big, 4 << 20, PLAN)
    c = data.fabricate_volume(str(tmp_path / "c"), 1, big + 1, 4 << 20, PLAN)
    assert a.offsets == b.offsets == c.offsets  # the same work for every seed
    assert np.array_equal(a.blob, b.blob) and not np.array_equal(a.blob, c.blob)


def test_each_client_draws_uniformly_from_a_seeded_stream_of_its_own():
    http_gets = harness.load_module("drivers", "http_gets")

    def draws(seed, client, n=50, count=3000):
        return list(itertools.islice(http_gets.client_draws(seed, client, n), count))

    big = 2**31 + 7  # the driver's seeds pass 32 signed bits
    assert draws(big, 0) == draws(big, 0)
    assert draws(big, 0) != draws(big + 1, 0)
    assert draws(big, 0) != draws(big, 1)
    got = draws(big, 3)
    assert set(got) == set(range(50))
    counts = np.bincount(got, minlength=50)
    assert counts.min() > 30 and counts.max() < 95  # 60 each, +- 4 sigma


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5
    assert spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert spread([100, 100, 100]) == 0
