"""Seconds the EC pipeline's I/O stages (`disk_read`, `write_sink`,
`fsync_publish`) took per GiB encoded, summed over the stages: they
overlap, so the sum can exceed the wall time."""

from ecbench.layerlib import stage_seconds_per_gib

STAGES = ("disk_read", "write_sink", "fsync_publish")


def read(obs, cell):
    return stage_seconds_per_gib(obs, STAGES)
