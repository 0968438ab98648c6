"""Seconds the EC pipeline's sink waited in `device_drain` for a batch
to come back from the device (upload, kernel and download, as far as
the stages before it do not hide them) per GiB turned over."""

from ecbench.layerlib import stage_seconds_per_gib


def read(obs, cell):
    return stage_seconds_per_gib(obs, ("device_drain",))
