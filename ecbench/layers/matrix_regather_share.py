"""Per cent of the window's reconstructions that asked peers for rows
(`peer_fetches_started` > 0 on their `ec.degraded_read` span) and
gathered their sibling matrix once more (`matrix_regathers` > 0): a
spare row stood where an open row's own fetch had not come good in
time, and the k rows were copied into a new matrix before the put. A
program that does not count it gives nothing to read."""

from ecbench.layerlib import walk


def read(obs, cell):
    reads = [
        d["attrs"] for root in obs.spans for d in walk(root) if d["op"] == "ec.degraded_read"
    ]
    asked = [a for a in reads if a.get("peer_fetches_started", 0) > 0]
    if not asked or not any("matrix_regathers" in a for a in reads):
        return None
    return 100.0 * sum(1 for a in asked if a.get("matrix_regathers", 0) > 0) / len(asked)
