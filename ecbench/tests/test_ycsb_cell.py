"""The cell `vol1g-10p4-node-down.ycsb-c`: its sampler against the
closed form, its configuration against the one it shares a volume with,
the plain decode against the plain encode, and its five readers on span
documents worked by hand and on ones recorded from a traced v5e run of
PR 29 (`ycsb_get.span_docs.json`: three healthy GETs, two that
reconstructed, two that hit the interval cache, two that waited on
another's build)."""

import json
import pathlib
import types

import numpy as np
import pytest

from ecbench import harness, reference_decode, spanlib
from ecbench import reference as R

HERE = pathlib.Path(__file__).resolve().parent
CELL = "vol1g-10p4-node-down.ycsb-c"
N, THETA = 1324, 0.99
NEW = ("reconstructing_get_share", "healthy_get_p50_ms", "shard_read_ms_per_get",
       "needle_parse_ms_per_get", "singleflight_wait_share")


@pytest.fixture(scope="module")
def ycsb():
    return harness.load_module("drivers", "http_gets_ycsb")


def reader(name):
    return harness.load_module("layers", name).read


def take(stream, n):
    return [next(stream) for _ in range(n)]


# ------------------------------------------------------------ the traffic


def test_the_closed_form_is_the_one_the_issue_sized_the_cell_with(ycsb):
    cdf = ycsb.zipf_cdf(N, THETA)
    assert cdf[0] == pytest.approx(0.125, abs=0.001)  # the hottest needle
    assert cdf[35] == pytest.approx(0.53, abs=0.005)  # the hottest 36
    assert cdf[99] == pytest.approx(0.66, abs=0.005)  # the hottest 100
    assert cdf[-1] == pytest.approx(1.0) and np.all(np.diff(cdf) > 0)
    # P(r) proportional to r ** -theta
    p = np.diff(np.concatenate([[0.0], cdf]))
    assert p[0] / p[9] == pytest.approx(10**THETA)


def test_the_sampler_draws_the_closed_form(ycsb):
    draws = 200_000
    ranks = np.array(take(ycsb.zipf_ranks(2**31 + 7, 3, N, THETA), draws))
    assert ranks.min() == 0 and ranks.max() <= N - 1
    p = np.diff(np.concatenate([[0.0], ycsb.zipf_cdf(N, THETA)]))
    counts = np.bincount(ranks, minlength=N)
    for r in range(20):  # the top ranks, each inside 3 sigma of a binomial
        sigma = (draws * p[r] * (1 - p[r])) ** 0.5
        assert abs(counts[r] - draws * p[r]) <= 3 * sigma, r
    # and the mass of the tail beyond them
    tail = draws * (1 - p[:100].sum())
    assert abs(counts[100:].sum() - tail) <= 3 * (tail * p[:100].sum()) ** 0.5


def test_the_same_seed_gives_the_same_stream_and_another_client_another(ycsb):
    a = take(ycsb.zipf_ranks(2**31 + 7, 0, N, THETA), 5000)
    assert a == take(ycsb.zipf_ranks(2**31 + 7, 0, N, THETA), 5000)
    assert a != take(ycsb.zipf_ranks(2**31 + 7, 1, N, THETA), 5000)
    assert a != take(ycsb.zipf_ranks(2**31 + 8, 0, N, THETA), 5000)
    # the warm-up's stream is no client's
    assert ycsb.WARM_CLIENT >= 64


def test_the_permutation_depends_on_the_popularity_seed_alone(ycsb):
    perm = ycsb.popularity(N, 24)
    assert sorted(perm.tolist()) == list(range(N))
    assert np.array_equal(perm, ycsb.popularity(N, 24))
    assert not np.array_equal(perm, ycsb.popularity(N, 25))
    traffic = harness.load_json(harness.HERE / "traffic" / "ycsb-c.json")
    assert traffic["popularity_seed"] == 24 and traffic["zipfian_constant"] == THETA
    # two run seeds: other requests, the same hot needle behind rank 0
    for seed in (1, 2**31 + 99):
        cell = types.SimpleNamespace(seed=seed, traffic=traffic)
        needles = take(ycsb.needle_stream(cell, 0, N), 4000)
        hottest = max(set(needles), key=needles.count)
        assert hottest == perm[0]
    one = take(ycsb.needle_stream(types.SimpleNamespace(seed=1, traffic=traffic), 0, N), 50)
    two = take(ycsb.needle_stream(types.SimpleNamespace(seed=2, traffic=traffic), 0, N), 50)
    assert one != two


# ------------------------------------------------------ the configuration


def test_the_deployment_is_vol1g_10p4_with_a_server_down():
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    down = harness.load_json(harness.HERE / "configs" / "vol1g-10p4-node-down.json")
    whole = harness.load_json(harness.HERE / "configs" / "vol1g-10p4.json")
    for key in ("volume_bytes", "volumes", "layout", "needles", "ec_backend",
                "ec_interval_cache_mb", "chips"):
        assert down[key] == whole[key], key  # set-up code and extents are shared
    place = down["placement"]
    k, m = down["layout"]["data_shards"], down["layout"]["parity_shards"]
    assert sorted(s for held in place["shards_of_server"] for s in held) == list(range(k + m))
    assert all(held == [i, i + 7] for i, held in enumerate(place["shards_of_server"]))
    assert down["lost_shards"] == place["shards_of_server"][down["down_server"]] == [1, 8]
    assert all(s < k for s in down["lost_shards"])  # two DATA shards
    assert down["reduced"] == ["volume_servers"] and "volume_servers" in down["cuts"]
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "vol1g-10p4-node-down"
    # first this cell's alone; since ISSUE 36 the spread cell, the same
    # reads with the shards on seven servers, reports them beside it
    spread = "vol1g-10p4-7vs-node-down.ycsb-c-spread"
    mine = {m["name"] for m in manifest["per_layer"] if m.get("workloads") == [CELL, spread]}
    assert mine == set(NEW)


def test_the_plain_decode_gives_back_what_the_plain_encode_took():
    rng = np.random.default_rng(29)
    dat = rng.integers(0, 256, 3 * 10 * 4096 + 777, dtype=np.uint8)
    layout = {"data_shards": 10, "parity_shards": 4, "large_block_bytes": 1 << 30,
              "small_block_bytes": 4096, "bitrot_block_bytes": 1 << 20,
              "bitrot_leaf_bytes": 4096}
    enc = R.encode(dat, layout)
    shards = {i: np.concatenate(enc.pieces[i]) for i in range(14)}
    for lost in ([1, 8], [3], [1, 8, 11], [0, 9, 10, 13]):
        left = {i: s for i, s in shards.items() if i not in lost}
        got = reference_decode.decode(left, lost, 10, 4)
        for sid in lost:
            assert np.array_equal(got[sid], shards[sid]), (lost, sid)
    # the rows used are the twelve left's first ten: two of them parity
    rows = reference_decode.decode_rows(10, 4, [0, 2, 3, 4, 5, 6, 7, 9, 10, 11], [1, 8])
    assert len(rows) == 2 and all(len(r) == 10 and any(r) for r in rows)
    with pytest.raises(ValueError):
        reference_decode.decode_rows(10, 4, [0, 2, 3], [1])


# ------------------------------------------------------------ the readers


def root(duration_s, stages=None, children=()):
    return {"op": "http.volume", "duration_s": duration_s, "attrs": {"op_class": "read"},
            "stages": {s: {"seconds": v, "count": 1, "chip": "", "cpu_s": 0.0}
                       for s, v in (stages or {}).items()},
            "events": [], "children": list(children)}


def degraded_read(duration_s, stages=(), events=()):
    return {"op": "ec.degraded_read", "duration_s": duration_s, "attrs": {},
            "stages": {s: {"seconds": 0.001, "count": 1, "chip": "", "cpu_s": 0.0}
                       for s in stages},
            "events": [{"ts": 0.0, "name": e, "attrs": {}} for e in events], "children": []}


def obs_of(*roots):
    obs = harness.Observed()
    obs.spans = list(roots)
    return obs


def test_the_readers_on_documents_worked_by_hand():
    healthy = {"volume.read": 0.010, "volume.read.index": 0.001, "volume.read.shard": 0.003,
               "volume.read.parse": 0.005}
    recovering = dict(healthy, **{"volume.read.recover": 0.050, "volume.read.shard": 0.001})
    obs = obs_of(
        root(0.020, healthy), root(0.030, healthy), root(0.040, healthy),
        root(0.100, recovering, [degraded_read(0.049, ("sibling_read", "reconstruct"))]),
        root(0.025, recovering, [degraded_read(0.001, events=("cache_hit",))]),
        root(0.090, recovering, [degraded_read(0.048, events=("singleflight_wait",))]),
        # a write's root is no GET
        {**root(9.0), "attrs": {"op_class": "write"}},
    )
    assert reader("reconstructing_get_share")(obs, None) == pytest.approx(100 / 6)
    assert reader("healthy_get_p50_ms")(obs, None) == pytest.approx(30.0)
    assert reader("shard_read_ms_per_get")(obs, None) == pytest.approx(1e3 * (3 * 0.003 + 3 * 0.001) / 6)
    assert reader("needle_parse_ms_per_get")(obs, None) == pytest.approx(5.0)
    assert reader("singleflight_wait_share")(obs, None) == pytest.approx(100 / 3)
    # a needle over two lost intervals, one rebuilt and one waited for
    two = root(0.2, recovering, [degraded_read(0.05, ("reconstruct",)),
                                 degraded_read(0.05, events=("singleflight_wait",))])
    assert reader("reconstructing_get_share")(obs_of(two), None) == 100.0
    assert reader("singleflight_wait_share")(obs_of(two), None) == 50.0
    assert reader("healthy_get_p50_ms")(obs_of(two), None) is None


def test_a_program_that_does_not_split_volume_read_gives_those_two_nothing():
    """The parent of PR 29: `volume.read` whole. The other three read
    spans, stages and events that were always there."""
    old = obs_of(
        root(0.020, {"volume.read": 0.010}),
        root(0.100, {"volume.read": 0.080}, [degraded_read(0.05, ("reconstruct",))]),
    )
    assert reader("shard_read_ms_per_get")(old, None) is None
    assert reader("needle_parse_ms_per_get")(old, None) is None
    assert reader("reconstructing_get_share")(old, None) == 50.0
    assert reader("healthy_get_p50_ms")(old, None) == pytest.approx(20.0)
    assert reader("singleflight_wait_share")(old, None) == 0.0
    for name in NEW:
        assert reader(name)(harness.Observed(), None) is None, name


@pytest.fixture(scope="module")
def recorded():
    return json.loads((HERE / "ycsb_get.span_docs.json").read_text())


def kind(doc):
    reads = [d for d in spanlib.walk(doc) if d["op"] == "ec.degraded_read"]
    if not reads:
        return "healthy"
    if any("reconstruct" in d["stages"] for d in reads):
        return "reconstruct"
    return {e["name"] for d in reads for e in d["events"]}.pop()


def test_the_recorded_gets_hold_the_parts_end_to_end(recorded):
    kinds = [kind(d) for d in recorded]
    assert {"healthy", "reconstruct", "cache_hit"} <= set(kinds)
    parts = ("volume.read.index", "volume.read.shard", "volume.read.recover",
             "volume.read.parse")
    for doc, what in zip(recorded, kinds):
        st = doc["stages"]
        assert ("volume.read.recover" in st) == (what != "healthy")
        assert "volume.read.index" in st and "volume.read.parse" in st
        total = sum(st[p]["seconds"] for p in parts if p in st)
        assert 0.9 * st["volume.read"]["seconds"] <= total <= st["volume.read"]["seconds"]
        ivs = sorted((t0, t1) for s, t0, t1, _th, _cpu in doc["intervals"] if s in parts)
        assert all(a1 == b0 for (_a0, a1), (b0, _b1) in zip(ivs, ivs[1:]))
        ((v0, v1),) = [(t0, t1) for s, t0, t1, _th, _cpu in doc["intervals"] if s == "volume.read"]
        assert v0 <= ivs[0][0] and ivs[-1][1] == v1
        for child in doc["children"]:  # inside the part that recovers
            assert any(
                s == "volume.read.recover" and t0 <= child["start_ns"] and child["end_ns"] <= t1
                for s, t0, t1, _th, _cpu in doc["intervals"]
            )


def test_the_readers_on_the_recorded_documents(recorded):
    obs = obs_of(*recorded)
    n = len(recorded)
    kinds = [kind(d) for d in recorded]
    assert reader("reconstructing_get_share")(obs, None) == pytest.approx(
        100.0 * kinds.count("reconstruct") / n
    )
    healthy = sorted(d["duration_s"] for d, k in zip(recorded, kinds) if k == "healthy")
    assert reader("healthy_get_p50_ms")(obs, None) == pytest.approx(
        1e3 * healthy[len(healthy) // 2]
    )
    for metric, stage in (("shard_read_ms_per_get", "volume.read.shard"),
                          ("needle_parse_ms_per_get", "volume.read.parse")):
        want = 1e3 * sum(d["stages"].get(stage, {"seconds": 0})["seconds"] for d in recorded) / n
        assert reader(metric)(obs, None) == pytest.approx(want) and want > 0
    degraded = n - kinds.count("healthy")
    assert reader("singleflight_wait_share")(obs, None) == pytest.approx(
        100.0 * kinds.count("singleflight_wait") / degraded
    )
    # the accepted readers read the same documents unchanged
    assert reader("reconstruct_ms_per_get")(obs, None) > 0
    assert reader("sibling_batched_share")(obs, None) == 100.0
    assert reader("get_cpu_ms_per_get")(obs, None) >= 0
