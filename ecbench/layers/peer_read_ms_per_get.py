"""Milliseconds in `peer_read` per GET of the window: what a GET's
entry server waited for shard ranges that its peers hold, the healthy
intervals of the needle (on the `http.volume` root) and the waits for a
reconstruction's sibling rows (on its `ec.degraded_read` span). A
program that has no such stage gives nothing to read."""

from ecbench.layerlib import get_roots
from ecbench.spanlib import has_stage, stage_ms_per_get


def read(obs, cell):
    if not has_stage(get_roots(obs), ("peer_read",)):
        return None
    return stage_ms_per_get(obs, "peer_read")
