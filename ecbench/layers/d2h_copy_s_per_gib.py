"""Seconds the sink spent copying a finished batch back
(`device_drain.d2h`, the device array into a numpy array, and
`device_drain.host_copy`, `np.ascontiguousarray` where it copies) per
GiB turned over: the download's share of `device_drain_s_per_gib`."""

from ecbench.spanlib import part_seconds_per_gib


def read(obs, cell):
    return part_seconds_per_gib(obs, ("device_drain.d2h", "device_drain.host_copy"))
