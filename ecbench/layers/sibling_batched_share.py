"""Per cent of the sibling rows of the window's reconstructions that
one batched native read filled (`sibling_rows_batched` on the
`ec.degraded_read` spans), the rest having been filled one at a time
(`sibling_rows_single`: a peer's copy, a row refilled after a failed
check, the native plane off). A program that counts neither gives
nothing to read."""

from ecbench.layerlib import get_roots, walk


def read(obs, cell):
    batched = single = 0
    for root in get_roots(obs):
        for d in walk(root):
            if d["op"] == "ec.degraded_read":
                batched += d["attrs"].get("sibling_rows_batched", 0)
                single += d["attrs"].get("sibling_rows_single", 0)
    if batched + single == 0:
        return None
    return 100.0 * batched / (batched + single)
