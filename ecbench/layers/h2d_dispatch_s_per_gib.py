"""Seconds in `h2d_dispatch` (host-to-device upload and the kernel's
dispatch) per GiB encoded."""

from ecbench.layerlib import stage_seconds_per_gib


def read(obs, cell):
    return stage_seconds_per_gib(obs, ("h2d_dispatch",))
