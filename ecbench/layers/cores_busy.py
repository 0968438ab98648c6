"""Cores the process kept busy over the window: its CPU seconds
(`process_cpu_ns` of the probe spans: every thread, native ones too)
over the spans' wall time."""

from ecbench import probelib


def read(obs, cell):
    cpu = probelib.cpu_seconds(obs)
    if cpu is None or cpu[2] <= 0:
        return None
    return cpu[1] / cpu[2]
