"""Milliseconds in `crc_verify` (the sidecar CRCs of the sibling
extents and of the reconstructed one) per GET of the window."""

from ecbench.spanlib import stage_ms_per_get


def read(obs, cell):
    return stage_ms_per_get(obs, "crc_verify")
