"""Median, over the window, of how late the program's native probe (a
thread that never touches Python) woke from a 5 ms sleep: ready to run
until it had a core. A sleeper that wakes pre-empts a busy thread most
of the time, so cores that are oversubscribed show in the tail before
they show here (the table on standard error has p95 and the mean)."""

from ecbench import probelib


def read(obs, cell):
    return probelib.median_wait_ms(obs, "core")
