"""Milliseconds of a GET outside its `ec.degraded_read` spans: the
client's wall time less the time under those spans, per GET. HTTP
parsing, needle assembly, the socket."""

from ecbench.layerlib import degraded_read_seconds, get_roots


def read(obs, cell):
    roots = get_roots(obs)
    if not roots or not obs.gets:
        return None
    wall = sum(t1 - t0 for t0, t1 in obs.gets)
    return 1e3 * (wall / len(obs.gets) - degraded_read_seconds(roots) / len(roots))
