"""Per cent of the bytes that the readers of the window's volume
operations landed in staged batches (`read_bytes` on their spans) that
landed in a matrix the program's batch pool had held
(`read_reused_bytes`): pages that an earlier batch of the process had
committed and mapped, where a fresh matrix is faulted in page by page
under the read. A program that counts no `read_bytes` gives nothing to
read."""

from ecbench.layerlib import walk
from ecbench.spanlib import window_op_roots


def read(obs, cell):
    attrs = [d["attrs"] for root in window_op_roots(obs) for d in walk(root)]
    landed = sum(a.get("read_bytes", 0) for a in attrs)
    if not landed:
        return None
    return 100.0 * sum(a.get("read_reused_bytes", 0) for a in attrs) / landed
