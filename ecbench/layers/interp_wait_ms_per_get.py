"""Milliseconds per GET that its worker thread, back from a native call
(the batched sibling read, the CRC of rows, the response's `sendv`),
waited to hold the interpreter again: `interp_wait_ns` on the spans
under the window's `http.volume` roots. The C side stamps the clock as
its last act and the wrapper reads it on return, so this is the real
thread at the real moment, not a probe's."""

from ecbench import probelib


def read(obs, cell):
    ns = probelib.seam_per_get(obs, "interp_wait_ns")
    return None if ns is None else ns / 1e6
