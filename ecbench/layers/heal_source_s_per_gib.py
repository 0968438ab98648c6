"""Seconds a heal waited for its rows from peers beyond the local read
that runs meanwhile (stage `source_land` of the `ec.peer_rebuild`
spans), per GiB of the heals' source rows, local ones included: a heal
whose holders answer as fast as its own disk reads nearly 0."""

from ecbench.harness import load_module
from ecbench.layerlib import GIB

H = load_module("layers", "heal_mb_per_s")


def read(obs, cell):
    spans = H.heals(obs)
    if not spans or not any("source_land" in d["stages"] for d in spans):
        return None
    waited = sum(d["stages"].get("source_land", {"seconds": 0.0})["seconds"] for d in spans)
    return waited / (sum(H.source_bytes(d) for d in spans) / GIB)
