"""From a `jax.profiler` trace to the device's busy time, the device
operations that took most of it, and the idle gaps laid to what the host
was doing. Two steps, so that the arithmetic can be checked on a small
recorded list of events (tests/trace_events.json) without a chip:

    load_events(xplane.pb) -> {"device": [...], "host": [...]}
    reduce_events(events)  -> busy_s, device_ops, idle_gaps

Busy is the union of the intervals in which any operation ran on a
device, whatever its name: a change of kernel keeps the number's
meaning. Host spans are the `ecbench.*` annotations that the drivers
write into the same trace (harness.annotate).
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
# a device plane's other lines repeat the operations at a coarser
# grain (whole programs, steps) or are host-side markers
OPS_LINE = "XLA Ops"
NOT_OPS_LINES = frozenset(
    {"Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
     "Framework Ops", "Source code", "Sparse Core Steps"}
)
# host annotations, first match wins when several cover one gap
HOST_LABELS = (
    ("ecbench.op.", "in_"),          # in_ec.encode, in_ec.rebuild
    ("ecbench.get", "a_degraded_GET_in_flight"),
    ("ecbench.reset", "in_the_reset_between_operations"),
)
NO_HOST_LABEL = "no_operation_in_flight"

Interval = tuple[float, float]


def union(intervals: list[Interval]) -> list[Interval]:
    out: list[Interval] = []
    for lo, hi in sorted(intervals):
        if hi <= lo:
            continue
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def length(intervals: list[Interval]) -> float:
    return sum(hi - lo for lo, hi in intervals)


def intersect(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """Of two sorted disjoint lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list[Interval], b: list[Interval]) -> list[Interval]:
    """a without b, both sorted and disjoint."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        t = j
        while t < len(b) and b[t][0] < hi:
            if b[t][0] > cur:
                out.append((cur, b[t][0]))
            cur = max(cur, b[t][1])
            t += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def short_name(name: str) -> str:
    """`%fusion.3 = u8[...] fusion(...)` -> `fusion.3`: the profiler
    names an operation by its whole HLO line."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def load_events(path: str) -> dict:
    """Device operations and `ecbench.*` host spans of one .xplane.pb,
    times in seconds."""
    import jax

    prof = jax.profiler.ProfileData.from_file(path)
    device, host, seen = [], [], []
    for plane in prof.planes:
        lines = list(plane.lines)
        seen.append([plane.name, [ln.name for ln in lines]])
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            named = [ln for ln in lines if ln.name == OPS_LINE]
            use = named or [ln for ln in lines if ln.name not in NOT_OPS_LINES]
            for ln in use:
                for ev in ln.events:
                    device.append(
                        [plane.name, short_name(ev.name), ev.start_ns / 1e9,
                         ev.duration_ns / 1e9]
                    )
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith("ecbench."):
                        host.append([ev.name, ev.start_ns / 1e9, ev.duration_ns / 1e9])
    return {"device": device, "host": host, "planes": seen}


def reduce_events(events: dict) -> dict:
    per_plane: dict[str, list[Interval]] = {}
    by_name: dict[str, float] = {}
    for plane, name, start, dur in events["device"]:
        per_plane.setdefault(plane, []).append((start, start + dur))
        by_name[name] = by_name.get(name, 0.0) + dur
    busy_each = {p: union(iv) for p, iv in per_plane.items()}
    busy_s = (
        sum(length(iv) for iv in busy_each.values()) / len(busy_each)
        if busy_each else 0.0
    )
    device_ops = sorted(([n, s] for n, s in by_name.items()), key=lambda r: -r[1])
    # gaps: where no device ran anything, over the span the trace covers
    any_busy = union([iv for ivs in busy_each.values() for iv in ivs])
    edges = [t for iv in any_busy for t in iv]
    edges += [t for _n, s, d in events["host"] for t in (s, s + d)]
    gaps: dict[str, float] = {}
    if edges:
        idle = subtract([(min(edges), max(edges))], any_busy)
        for prefix, label in HOST_LABELS:
            groups: dict[str, list[Interval]] = {}
            for name, s, d in events["host"]:
                if name.startswith(prefix):
                    key = label + name[len(prefix):] if label.endswith("_") else label
                    groups.setdefault(key, []).append((s, s + d))
            for key, ivs in groups.items():
                cover = union(ivs)
                gaps[key] = gaps.get(key, 0.0) + length(intersect(idle, cover))
                idle = subtract(idle, cover)
        gaps[NO_HOST_LABEL] = length(idle)
    idle_gaps = sorted(
        ([n, s] for n, s in gaps.items() if s > 0), key=lambda r: -r[1]
    )
    return {
        "busy_s": busy_s,
        "devices": len(busy_each),
        "device_ops": device_ops,
        "idle_gaps": idle_gaps,
    }


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not paths:
        raise FileNotFoundError(f"the profiler left no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str) -> dict:
    events = load_events(newest_xplane(trace_dir))
    out = reduce_events(events)
    out["planes"] = events["planes"]
    return out
