"""Per cent of the bytes a heal read from peers that came over the
holders' native shard planes (`source_bytes_plane`), the rest over the
`VolumeEcShardRead` stream (`source_bytes_stream`: holders without a
plane, and the one-granule re-reads)."""

from ecbench.harness import load_module

H = load_module("layers", "heal_mb_per_s")


def read(obs, cell):
    spans = H.heals(obs)
    plane = sum(int(d["attrs"].get("source_bytes_plane", 0)) for d in spans)
    stream = sum(int(d["attrs"].get("source_bytes_stream", 0)) for d in spans)
    if plane + stream <= 0:
        return None
    return 100.0 * plane / (plane + stream)
