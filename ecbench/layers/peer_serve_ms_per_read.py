"""Mean length in milliseconds of the window's `rpc.ec_shard_read`
root spans: the HOLDER's side of a read from a peer, from the request's
arrival in its gRPC pool to the end of the stream, read or unread.
Nothing to read where no peer was asked."""


def read(obs, cell):
    served = [d["duration_s"] for d in obs.spans if d["op"] == "rpc.ec_shard_read"]
    if not served:
        return None
    return 1e3 * sum(served) / len(served)
