"""Per cent of the bytes that the window's volume operations fetched
from the device (`d2h_bytes` on their spans) that came home as dense
32-bit words (`d2h_dense_bytes`): a uint8 result lies on the chip four
rows to a word, and what rows it lacks cross the link as holes. A
program that counts no `d2h_dense_bytes` gives nothing to read."""

from ecbench.layerlib import walk
from ecbench.spanlib import window_op_roots


def read(obs, cell):
    attrs = [d["attrs"] for root in window_op_roots(obs) for d in walk(root)]
    fetched = sum(a.get("d2h_bytes", 0) for a in attrs)
    if not fetched or not any("d2h_dense_bytes" in a for a in attrs):
        return None
    return 100.0 * sum(a.get("d2h_dense_bytes", 0) for a in attrs) / fetched
