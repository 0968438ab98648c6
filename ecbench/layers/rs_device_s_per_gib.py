"""Seconds the device was busy (union of all its operations in the
traced slice, whatever their names) per GiB of volume operations that
fell into the slice."""

from ecbench.layerlib import GIB, bytes_in_slice


def read(obs, cell):
    nbytes = bytes_in_slice(obs)
    if obs.device is None or nbytes <= 0 or obs.device["busy_s"] <= 0:
        return None
    return obs.device["busy_s"] / (nbytes / GIB)
