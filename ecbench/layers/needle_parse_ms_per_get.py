"""Milliseconds a GET of the window spent joining its intervals and in
`Needle.from_bytes`, the body's CRC included (`volume.read.parse`, a
part of the handler's `volume.read` stage), per GET. A program that
does not split `volume.read` gives nothing to read."""

from ecbench.layerlib import get_roots
from ecbench.spanlib import has_stage, stage_ms_per_get


def read(obs, cell):
    if not has_stage(get_roots(obs), ("volume.read.parse",)):
        return None
    return stage_ms_per_get(obs, "volume.read.parse")
