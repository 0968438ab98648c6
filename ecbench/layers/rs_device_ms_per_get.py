"""Milliseconds the device was busy (union of all its operations in
the traced slice) per GET that finished inside the slice."""

from ecbench.layerlib import gets_in


def read(obs, cell):
    if obs.device is None or obs.slice_t is None or obs.device["busy_s"] <= 0:
        return None
    n = gets_in(obs, *obs.slice_t)
    return 1e3 * obs.device["busy_s"] / n if n else None
