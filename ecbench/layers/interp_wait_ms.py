"""Median, over the window, of how late the program's Python probe woke
from a 5 ms sleep (the `py_samples` of its `interp.probe` spans): a
thread that is ready to run waits so long for a core and then for the
interpreter. `core_wait_ms` is the same thread's twin without the
interpreter; the difference is the queue for the interpreter itself.
The tables of both probes go to standard error."""

from ecbench import probelib


def read(obs, cell):
    probelib.describe(obs)
    return probelib.median_wait_ms(obs, "py")
