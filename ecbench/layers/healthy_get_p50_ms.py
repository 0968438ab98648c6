"""Median duration in milliseconds of the `http.volume` root spans of
the GETs that recovered nothing (no `ec.degraded_read` span under
them): what a healthy GET costs at the server while reconstructions
share its workers and its interpreter."""

import statistics

from ecbench.layerlib import get_roots, walk


def read(obs, cell):
    healthy = [
        r["duration_s"] for r in get_roots(obs)
        if not any(d["op"] == "ec.degraded_read" for d in walk(r))
    ]
    return 1e3 * statistics.median(healthy) if healthy else None
