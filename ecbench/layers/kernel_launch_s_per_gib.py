"""Seconds the dispatcher spent in `h2d_dispatch.launch` (the jitted
apply: coefficient bits, the executable's look-up, the enqueue) per GiB
turned over: the launch's share of `h2d_dispatch_s_per_gib`."""

from ecbench.spanlib import part_seconds_per_gib


def read(obs, cell):
    return part_seconds_per_gib(obs, ("h2d_dispatch.launch",))
