"""Milliseconds a GET's readable connection sat in the pooled front
end's ready queue before a worker took it (`ready_wait`), per GET."""

from ecbench.layerlib import get_roots
from ecbench.spanlib import has_stage, stage_ms_per_get


def read(obs, cell):
    # `parse` marks a program that stamps the queue at all: a GET served
    # without queueing has no `ready_wait`, and that reads 0
    if not has_stage(get_roots(obs), ("parse",)):
        return None
    return stage_ms_per_get(obs, "ready_wait")
