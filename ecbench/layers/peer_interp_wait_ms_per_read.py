"""Milliseconds that the two native calls of a read from a peer waited
to hold the interpreter again, per read: `interp_wait_ns` on the read's
own span (the stamped return of `sn_recv_into`) plus that on the
holder's span joined to it (the stamped return of `sn_send_file`).
Python's own `recv` and `sendall` on the way are not stamped. "Per
read" as `peer_request_ms_per_read` says."""

from ecbench.harness import load_module

_shared = load_module("layers", "peer_request_ms_per_read")


def read(obs, cell):
    got = _shared.reads(obs)
    if not got:
        return None
    holders = {r["span_id"]: hs for r, hs in _shared.joined(obs)}
    waited = sum(
        r["attrs"].get("interp_wait_ns", 0)
        + sum(h["attrs"].get("interp_wait_ns", 0) for h in holders.get(r.get("span_id"), ()))
        for r in got
    )
    return waited / 1e6 / len(got)
