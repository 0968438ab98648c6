"""Per cent of the fetches that the window's reconstructions started at
their peers whose answer was not needed (`peer_fetches_unused` over
`peer_fetches_started` on the `ec.degraded_read` spans): every shard
with a holder is asked at once and the matrix is full after k rows, so
the rest of the peers' streams are read by nobody. A program that does
not count them gives nothing to read."""

from ecbench.layerlib import get_roots, walk


def read(obs, cell):
    started = unused = 0
    for root in get_roots(obs):
        for d in walk(root):
            if d["op"] == "ec.degraded_read":
                started += d["attrs"].get("peer_fetches_started", 0)
                unused += d["attrs"].get("peer_fetches_unused", 0)
    if started == 0:
        return None
    return 100.0 * unused / started
