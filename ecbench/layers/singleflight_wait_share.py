"""Per cent of the window's `ec.degraded_read` spans that waited on
another GET's reconstruction of the same extent (the span's
`singleflight_wait` event) where they would otherwise have built it
again: hot keys on a lost shard."""

from ecbench.layerlib import get_roots, walk


def read(obs, cell):
    reads = [
        d for r in get_roots(obs) for d in walk(r) if d["op"] == "ec.degraded_read"
    ]
    if not reads:
        return None
    waited = sum(
        any(e["name"] == "singleflight_wait" for e in d.get("events", ())) for d in reads
    )
    return 100.0 * waited / len(reads)
