"""Milliseconds of a GET's `http.volume` root span that no child span
covers (its self time): parsing, the needle's assembly, the response,
seen from inside the server, where `frontend_ms_per_get` is the client's
wall less the degraded read."""

from ecbench.layerlib import get_roots
from ecbench.spanlib import self_seconds


def read(obs, cell):
    own = [self_seconds(r) for r in get_roots(obs)]
    if not own or None in own:
        return None
    return 1e3 * sum(own) / len(own)
