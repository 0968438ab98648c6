"""Milliseconds inside `ec.degraded_read` spans (sibling reads, CRC
checks, admission, the reconstruction itself) per GET of the window."""

from ecbench.layerlib import degraded_read_seconds, get_roots


def read(obs, cell):
    roots = get_roots(obs)
    if not roots:
        return None
    return 1e3 * degraded_read_seconds(roots) / len(roots)
