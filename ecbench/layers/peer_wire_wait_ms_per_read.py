"""Milliseconds a read from a peer spends in neither side's span, per
read that found its holder's span by id: the read's duration, less its
`fetch_queue`, less the holder's `rpc.ec_shard_read` (all of them where
the reader asked more than once). What is left is the request on its
way, the bytes in the sockets' buffers, and both threads' waits for
their interpreters outside the holder's span. A difference of
DURATIONS, each taken on its own side's clock: true where reader and
holder do not share one."""

from ecbench.harness import load_module

_shared = load_module("layers", "peer_request_ms_per_read")


def read(obs, cell):
    pairs = _shared.joined(obs)
    if not pairs:
        return None
    outside = sum(
        r["duration_s"] - r["stages"].get("fetch_queue", {}).get("seconds", 0.0)
        - sum(h["duration_s"] for h in holders)
        for r, holders in pairs
    )
    return 1e3 * outside / len(pairs)
