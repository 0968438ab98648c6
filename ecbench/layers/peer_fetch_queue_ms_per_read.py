"""Milliseconds in `fetch_queue` per sibling row read from a peer: from
the reconstruction's `submit` to the fetch pool until a thread of the
pool runs the fetch's first instruction. Over the reads of `kind`
`sibling` alone: an interval is read by the GET's own worker and waits
for no pool. "Per read" as `peer_request_ms_per_read` says."""

from ecbench.harness import load_module

_shared = load_module("layers", "peer_request_ms_per_read")


def read(obs, cell):
    return _shared.stage_ms_per_read(obs, "fetch_queue", kind="sibling")
