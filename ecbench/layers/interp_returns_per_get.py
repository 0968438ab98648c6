"""Returns to the interpreter from a native call per GET
(`interp_returns` on the spans under the window's `http.volume` roots):
how often a GET pays `interp_wait_ms_per_get`'s price."""

from ecbench import probelib


def read(obs, cell):
    return probelib.seam_per_get(obs, "interp_returns")
