"""Seconds the dispatcher spent handing a batch's bytes to the device
(`h2d_dispatch.stage`, the contiguous host copy, and `h2d_dispatch.put`,
`jax.device_put`) per GiB turned over: the upload's share of
`h2d_dispatch_s_per_gib`."""

from ecbench.spanlib import part_seconds_per_gib


def read(obs, cell):
    return part_seconds_per_gib(obs, ("h2d_dispatch.stage", "h2d_dispatch.put"))
