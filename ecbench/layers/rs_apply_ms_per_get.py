"""Milliseconds in `reconstruct` (the single-shot Reed-Solomon apply:
stacking, the launch, the wait, the copy back) per GET of the window."""

from ecbench.spanlib import stage_ms_per_get


def read(obs, cell):
    return stage_ms_per_get(obs, "reconstruct")
