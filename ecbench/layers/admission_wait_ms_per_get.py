"""Milliseconds a degraded GET's reconstruction waited for admission to
the device queue, per GET of the window."""

from ecbench.layerlib import get_roots, stage_seconds


def read(obs, cell):
    roots = get_roots(obs)
    if not roots:
        return None
    return 1e3 * stage_seconds(roots, ("admission_wait",)) / len(roots)
