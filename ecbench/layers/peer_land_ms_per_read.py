"""Milliseconds in `payload_land` per read from a peer: the range
landing in the buffer it is used in (`sn_recv_into` with the granule
CRCs rolled on the way, or the Python loop), the connection going back
to the pool, and the return to the interpreter. "Per read" as
`peer_request_ms_per_read` says."""

from ecbench.harness import load_module

_shared = load_module("layers", "peer_request_ms_per_read")


def read(obs, cell):
    return _shared.stage_ms_per_read(obs, "payload_land")
