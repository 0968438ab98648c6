"""Seconds the sink was blocked in `device_drain.ready`, until a batch's
result existed on the device (its upload, the kernel, the device's own
queue), per GiB turned over: the wait's share of
`device_drain_s_per_gib`."""

from ecbench.spanlib import part_seconds_per_gib


def read(obs, cell):
    return part_seconds_per_gib(obs, ("device_drain.ready",))
