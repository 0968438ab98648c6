"""`core_wait_ms` over the samples that woke while a rebuild's pipeline
ran (see `interp_wait_in_pipeline_ms`): the native probe's wait for a
core beside ten native reader threads, the sink and the runtime's
transfers."""

from ecbench import probelib


def read(obs, cell):
    intervals = probelib.pipeline_intervals(obs)
    if intervals is None:
        return None
    return probelib.median_wait_ms(obs, "core", intervals)
