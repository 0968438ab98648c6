"""Per cent of the window's reconstructing GETs (`http.volume` roots
whose `ec.degraded_read` ran a `reconstruct` stage) that found every
slot of the device queue's window taken when they asked for theirs, most
of them by recovery batches: the span's `window_full` event with `by` =
`recovery`. A program whose queue does not say who held its window (no
slot-seconds in the driver's counters) gives nothing to read."""

from ecbench.layerlib import get_roots, walk


def blocked_by_recovery(root: dict) -> list[dict]:
    """The root's `ec.degraded_read` spans that hold the event."""
    return [
        d for d in walk(root)
        if d["op"] == "ec.degraded_read" and any(
            e["name"] == "window_full" and e["attrs"].get("by") == "recovery"
            for e in d.get("events", ())
        )
    ]


def read(obs, cell):
    if "queue_slot_seconds" not in obs.counters:
        return None
    ran = [
        r for r in get_roots(obs)
        if any(d["op"] == "ec.degraded_read" and "reconstruct" in d["stages"] for d in walk(r))
    ]
    if not ran:
        return None
    return 100.0 * sum(1 for r in ran if blocked_by_recovery(r)) / len(ran)
