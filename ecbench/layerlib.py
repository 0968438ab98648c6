"""What the per-layer readers share: walking span trees, the bytes and
the operations of the window and of the traced slice."""

from __future__ import annotations

GIB = 1 << 30

VOLUME_OP_ROOTS = ("rpc.ec_shards_generate", "rpc.ec_shards_rebuild")


def walk(doc: dict):
    stack = [doc]
    while stack:
        d = stack.pop()
        yield d
        stack.extend(d["children"])


def stage_seconds(docs, stages) -> float:
    return sum(
        acc["seconds"]
        for root in docs for d in walk(root)
        for s, acc in d["stages"].items() if s in stages
    )


def volume_op_roots(obs) -> list[dict]:
    return [d for d in obs.spans if d["op"] in VOLUME_OP_ROOTS]


def get_roots(obs) -> list[dict]:
    """Root spans of the volume server's needle GETs."""
    return [
        d for d in obs.spans
        if d["op"] == "http.volume" and d["attrs"].get("op_class") == "read"
    ]


def degraded_read_seconds(roots) -> float:
    """Seconds under `ec.degraded_read` spans of these GET roots."""
    return sum(
        d["duration_s"] for r in roots for d in walk(r) if d["op"] == "ec.degraded_read"
    )


def done_ops(obs) -> list[tuple]:
    return [o for o in obs.ops if o[0] == "op"]


def stage_seconds_per_gib(obs, stages) -> float | None:
    """Stage seconds of the window's volume operations per GiB they
    turned over; None where the window has no such span."""
    roots = volume_op_roots(obs)
    if not roots or not obs.bytes:
        return None
    return stage_seconds(roots, stages) / (obs.bytes / GIB)


def gets_in(obs, t0: float, t1: float) -> int:
    return sum(1 for _s, e in obs.gets if t0 <= e <= t1)


def bytes_in_slice(obs) -> float:
    """Bytes of the volume operations that fall into the traced slice:
    an operation that lies half inside counts half."""
    if obs.slice_t is None:
        return 0.0
    lo, hi = obs.slice_t
    total = 0.0
    for _kind, _vid, t0, t1, nbytes in done_ops(obs):
        inside = min(t1, hi) - max(t0, lo)
        if inside > 0 and t1 > t0:
            total += nbytes * inside / (t1 - t0)
    return total


def describe_volume_ops(obs) -> str:
    """One traced run's volume operations, each with the seconds of its
    root span and of its stages: where a slow operation lost its time."""
    out = []
    for root in volume_op_roots(obs)[-len(done_ops(obs)):]:
        stages: dict[str, float] = {}
        for d in walk(root):
            for s, acc in d["stages"].items():
                stages[s] = stages.get(s, 0.0) + acc["seconds"]
        out.append(
            f"{root['duration_s']:.3f}("
            + " ".join(f"{s}={t:.3f}" for s, t in sorted(stages.items())) + ")"
        )
    return " ".join(out)
