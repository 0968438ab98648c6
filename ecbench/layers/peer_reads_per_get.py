"""Shard ranges asked of peers per GET of the window: the intervals a
GET's entry server read from a peer (`peer_reads` on the `http.volume`
root) and the fetches its reconstructions started (`peer_fetches_started`
on the `ec.degraded_read` spans). A program that counts neither gives
nothing to read."""

from ecbench.layerlib import get_roots, walk

COUNTS = ("peer_reads", "peer_fetches_started")


def read(obs, cell):
    roots = get_roots(obs)
    counts = [d["attrs"][c] for r in roots for d in walk(r) for c in COUNTS if c in d["attrs"]]
    if not counts:
        return None
    return sum(counts) / len(roots)
