"""Per cent of the holders' `rpc.ec_shard_read` seconds that lie in
`stream.sendfile`, the range leaving (`sendfile`, or the Python egress
loop): the rest of a holder's span is its look-up of the shard
(`stream.resolve`), its header's `sendall` (`stream.header`) and what
ran before the first of them. A program whose holders do not split
`stream` gives nothing to read."""

from ecbench.harness import load_module

SERVE_OP = load_module("layers", "peer_request_ms_per_read").SERVE_OP


def read(obs, cell):
    served = [d for d in obs.spans if d["op"] == SERVE_OP]
    if not any("stream.sendfile" in d["stages"] for d in served):
        return None
    whole = sum(d["duration_s"] for d in served)
    if whole <= 0:
        return None
    sending = sum(d["stages"].get("stream.sendfile", {}).get("seconds", 0.0) for d in served)
    return 100.0 * sending / whole
