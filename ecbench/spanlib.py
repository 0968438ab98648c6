"""What the readers of the program's own timeline share: the stage
intervals and CPU seconds that span documents carry, and the `sw:`
annotations that the program writes into the profiler's trace beside
the device's operations.

A program without them (a span document with no `intervals`, a trace
with no `sw:` event) gives every function here nothing to read: it
returns None, and the reader leaves its metric out.
"""

from __future__ import annotations

import sys

from ecbench import harness, tracered
from ecbench.layerlib import (
    done_ops, get_roots, stage_seconds, stage_seconds_per_gib, volume_op_roots, walk,
)

# the Reed-Solomon kernel's one name among the device operations
# (seaweedfs_tpu/ops/rs_jax.py KERNEL_NAME; XLA appends `.N`)
KERNEL_NAME = "sw_rs_apply"
SW_PREFIX = "sw:"
NS = 1e9


# ------------------------------------------------------------ span trees


def window_op_roots(obs) -> list[dict]:
    """Root spans of the window's volume operations (the warm-up's come
    first in the ring)."""
    ops = done_ops(obs)
    return volume_op_roots(obs)[-len(ops):] if ops else []


def has_stage(roots, stages) -> bool:
    return any(s in d["stages"] for r in roots for d in walk(r) for s in stages)


def part_seconds_per_gib(obs, stages) -> float | None:
    """Seconds of sub-stages per GiB the window's volume operations
    turned over; None where no span recorded any of them."""
    if not has_stage(volume_op_roots(obs), stages):
        return None
    return stage_seconds_per_gib(obs, stages)


def stage_ms_per_get(obs, stage: str) -> float | None:
    """Milliseconds in `stage` per GET of the window."""
    roots = get_roots(obs)
    if not roots:
        return None
    return 1e3 * stage_seconds(roots, (stage,)) / len(roots)


def stage_intervals(root: dict, stage: str) -> list[tuple[float, float]]:
    """[start, end) in seconds of every entry of `stage` anywhere in the
    tree: spans of one process share one clock."""
    return [
        (t0 / NS, t1 / NS)
        for d in walk(root)
        for name, t0, t1, _thread, _cpu in d.get("intervals", ())
        if name == stage
    ]


def stage_share_of_wall(obs, stage: str) -> float | None:
    """Per cent of the operations' wall time during which some thread
    was inside `stage`: the union of its intervals over the root spans'
    durations, summed over the window's operations."""
    roots = window_op_roots(obs)
    if not roots or not any("intervals" in r for r in roots):
        return None
    covered = sum(tracered.length(tracered.union(stage_intervals(r, stage))) for r in roots)
    wall = sum(r["duration_s"] for r in roots)
    return 100.0 * covered / wall if wall > 0 else None


def tree_cpu_seconds(root: dict) -> float | None:
    """CPU seconds the tree's spans and stages read, all threads, each
    second once: a span claims its owning thread from start to end, a
    stage interval its thread for its length, and a claim that lies
    inside another claim of the same thread (a stage inside its span, a
    sub-stage inside its parent) is already counted there. None where
    no span carries a CPU reading."""
    claims: dict[str, list[tuple[int, int, float]]] = {}
    for d in walk(root):
        if d.get("cpu_s") is not None:
            claims.setdefault(d["thread"], []).append(
                (d["start_ns"], d["end_ns"], d["cpu_s"])
            )
        for _name, t0, t1, thread, cpu_ns in d.get("intervals", ()):
            if cpu_ns >= 0:
                claims.setdefault(thread, []).append((t0, t1, cpu_ns / NS))
    if not claims:
        return None
    total = 0.0
    for mine in claims.values():
        outer_end = -1
        for t0, t1, cpu in sorted(mine, key=lambda c: (c[0], -c[1])):
            if t1 <= outer_end:
                continue  # inside the claim before it
            total += cpu
            outer_end = t1
    return total


def self_seconds(root: dict) -> float | None:
    """The root span's duration less the part of it that its child
    spans cover (choosing-metrics guide, section 4)."""
    if "start_ns" not in root:
        return None
    lo, hi = root["start_ns"] / NS, root["end_ns"] / NS
    kids = tracered.union(
        [(c["start_ns"] / NS, c["end_ns"] / NS) for c in root["children"]]
    )
    return (hi - lo) - tracered.length(tracered.intersect(kids, [(lo, hi)]))


# ------------------------------------------------- the profiler's trace


def load_events(path: str) -> dict:
    """Device operations, and the host's `sw:` and `ecbench.` spans, of
    one .xplane.pb; times in seconds on the trace's one clock."""
    import jax

    prof = jax.profiler.ProfileData.from_file(path)
    device, host = [], []
    for plane in prof.planes:
        lines = list(plane.lines)
        if plane.name.startswith(tracered.DEVICE_PLANE_PREFIX):
            named = [ln for ln in lines if ln.name == tracered.OPS_LINE]
            use = named or [ln for ln in lines if ln.name not in tracered.NOT_OPS_LINES]
            for ln in use:
                for ev in ln.events:
                    device.append(
                        [plane.name, tracered.short_name(ev.name), ev.start_ns / NS,
                         ev.duration_ns / NS]
                    )
        elif plane.name.startswith("/host:"):
            for row, ln in enumerate(lines):
                for ev in ln.events:
                    if ev.name.startswith((SW_PREFIX, "ecbench.")):
                        host.append([ev.name, row, ev.start_ns / NS, ev.duration_ns / NS])
    return {"device": device, "host": host}


def slice_events(obs) -> dict | None:
    """Events of the run's traced slice, whose .xplane.pb is still on
    disk while the readers run; None where no slice was taken."""
    if obs.device is None:
        return None
    try:
        return load_events(tracered.newest_xplane(str(harness.TRACE_DIR)))
    except FileNotFoundError:
        return None


def idle_attribution(events: dict) -> dict | None:
    """Lay the device's idle time to the program's stages. Idle is the
    span the events cover less the union of all device operations; a
    stage is an `sw:<op>/<stage>` event (an `sw:<op>` span is open all
    through an operation and attributes nothing). Returns idle_s, the
    idle seconds with no stage open on any thread (unattributed_s), and
    idle seconds by stage open, which sum to more than the idle time
    where threads overlap. None without a device operation or without
    any `sw:` event."""
    stages: dict[str, list] = {}
    edges = []
    for name, _row, start, dur in events["host"]:
        edges += [start, start + dur]
        if name.startswith(SW_PREFIX) and "/" in name:
            stages.setdefault(name[len(SW_PREFIX):], []).append((start, start + dur))
    busy = tracered.union([(s, s + d) for _p, _n, s, d in events["device"]])
    if not busy or not any(n.startswith(SW_PREFIX) for n, *_ in events["host"]):
        return None
    edges += [t for iv in busy for t in iv]
    idle = tracered.subtract([(min(edges), max(edges))], busy)
    by_stage = {
        name: tracered.length(tracered.intersect(idle, tracered.union(ivs)))
        for name, ivs in stages.items()
    }
    covered = tracered.union([iv for ivs in stages.values() for iv in ivs])
    return {
        "idle_s": tracered.length(idle),
        "unattributed_s": tracered.length(tracered.subtract(idle, covered)),
        "by_stage": dict(sorted(by_stage.items(), key=lambda kv: -kv[1])),
    }


def idle_unattributed_share(obs) -> float | None:
    """Per cent of the slice's device-idle time during which no stage of
    the program was open; the table of idle seconds by stage goes to
    standard error."""
    events = slice_events(obs)
    found = idle_attribution(events) if events else None
    if found is None or found["idle_s"] <= 0:
        return None
    print(
        f"ecbench: device idle {found['idle_s']:.4f} s of the slice, "
        f"{found['unattributed_s']:.4f} s with no sw: stage open; idle seconds by "
        "stage open (threads overlap, so the sum may pass the idle time): "
        + " ".join(f"{n}={s:.4f}" for n, s in found["by_stage"].items() if s > 0),
        file=sys.stderr, flush=True,
    )
    return 100.0 * found["unattributed_s"] / found["idle_s"]
