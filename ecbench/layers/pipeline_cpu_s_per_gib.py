"""CPU seconds (`time.thread_time`) that the spans and stages of the
window's volume operations read, over all the program's Python threads,
per GiB turned over. Wall less CPU is time a thread did not run. The
native sink's per-shard writer threads are not Python threads and are
not in it."""

from ecbench.layerlib import GIB
from ecbench.spanlib import tree_cpu_seconds, window_op_roots


def read(obs, cell):
    cpu = [tree_cpu_seconds(r) for r in window_op_roots(obs)]
    if not cpu or None in cpu or not obs.bytes:
        return None
    return sum(cpu) / (obs.bytes / GIB)
