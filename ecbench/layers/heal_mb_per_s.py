"""The pace of the window's heals from peers: the source bytes of every
`ec.peer_rebuild` span (its k rows: `source_bytes_plane` +
`source_bytes_stream` + `source_bytes_local`) over the union of the
spans' walls, in MB/s (MB = 1e6). A heal beside reads is judged by the
reads; its pace is what this reads. A program whose heal keeps no such
attributes gives nothing to read."""

from ecbench import tracered
from ecbench.layerlib import walk
from ecbench.spanlib import NS

SOURCES = ("source_bytes_plane", "source_bytes_stream", "source_bytes_local")


def heals(obs) -> list[dict]:
    """The window's heals that read sources: `ec.peer_rebuild` spans,
    wherever they hang (under the rebuilder's `rpc.ec_shards_rebuild`)."""
    return [
        d for root in obs.spans for d in walk(root)
        if d["op"] == "ec.peer_rebuild" and source_bytes(d) > 0
    ]


def source_bytes(d: dict) -> int:
    return sum(int(d["attrs"].get(a, 0)) for a in SOURCES)


def read(obs, cell):
    spans = [d for d in heals(obs) if d["end_ns"] > d["start_ns"]]
    if not spans:
        return None
    wall = tracered.length(
        tracered.union([(d["start_ns"] / NS, d["end_ns"] / NS) for d in spans])
    )
    return sum(source_bytes(d) for d in spans) / 1e6 / wall
