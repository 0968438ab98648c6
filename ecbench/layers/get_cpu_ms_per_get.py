"""CPU milliseconds (`time.thread_time`) of the worker thread under a
GET's `http.volume` root span, per GET: the work, where the span's
duration is work and waiting (for a core, the GIL, a lock, the device)."""

from ecbench.layerlib import get_roots


def read(obs, cell):
    cpu = [r.get("cpu_s") for r in get_roots(obs)]
    if not cpu or None in cpu:
        return None
    return 1e3 * sum(cpu) / len(cpu)
