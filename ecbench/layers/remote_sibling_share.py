"""Per cent of the sibling rows of the window's reconstructions that
came from peers and passed the `.ecsum` check (`sibling_rows_remote`
over `sibling_rows_batched` + `sibling_rows_single` on the
`ec.degraded_read` spans). A program that does not count the peers'
rows gives nothing to read."""

from ecbench.layerlib import get_roots, walk


def read(obs, cell):
    reads = [
        d["attrs"] for root in get_roots(obs) for d in walk(root)
        if d["op"] == "ec.degraded_read"
    ]
    rows = sum(a.get("sibling_rows_batched", 0) + a.get("sibling_rows_single", 0) for a in reads)
    if rows == 0 or not any("sibling_rows_remote" in a for a in reads):
        return None
    return 100.0 * sum(a.get("sibling_rows_remote", 0) for a in reads) / rows
