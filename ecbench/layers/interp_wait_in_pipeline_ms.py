"""`interp_wait_ms` over the samples that woke while a rebuild's
pipeline ran: inside the union of the `disk_read` intervals of the
window's `ec.rebuild` operations (what `reader_busy_share` reads). Beside
`core_wait_in_pipeline_ms` it says whether the reads lose the interpreter
or cores to a rebuild."""

from ecbench import probelib


def read(obs, cell):
    intervals = probelib.pipeline_intervals(obs)
    if intervals is None:
        return None
    return probelib.median_wait_ms(obs, "py", intervals)
