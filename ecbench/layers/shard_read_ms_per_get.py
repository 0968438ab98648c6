"""Milliseconds a GET of the window spent reading the healthy intervals
of its needle from mounted shards (`volume.read.shard`, a part of the
handler's `volume.read` stage), per GET. An interval that a peer holds
lay here too until PR 35 gave it a part of its own (`volume.read.peer`:
`peer_read_ms_per_get`); in a cell whose shards are spread this is the
entry server's own two. A program that does not split `volume.read`
gives nothing to read."""

from ecbench.layerlib import get_roots
from ecbench.spanlib import has_stage, stage_ms_per_get


def read(obs, cell):
    # a needle that lies wholly on lost shards has no `.shard` part, but
    # every needle read ends in `.parse`: that marks a program with parts
    if not has_stage(get_roots(obs), ("volume.read.parse",)):
        return None
    return stage_ms_per_get(obs, "volume.read.shard")
