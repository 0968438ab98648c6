"""Milliseconds in `sibling_read` (the reads of the ten surviving
shards' extents) per GET of the window."""

from ecbench.spanlib import stage_ms_per_get


def read(obs, cell):
    return stage_ms_per_get(obs, "sibling_read")
