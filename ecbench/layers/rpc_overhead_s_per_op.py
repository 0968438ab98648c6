"""What the shell and the RPC layer add to a volume operation: its wall
time at the shell less its root span on the volume server
(`rpc.ec_shards_generate` / `rpc.ec_shards_rebuild`), a mean over the
window's operations."""

from ecbench.layerlib import done_ops, volume_op_roots


def read(obs, cell):
    ops, roots = done_ops(obs), volume_op_roots(obs)
    if not ops or len(roots) < len(ops):
        return None
    # the warm-up's roots come first in the ring: the window's are the last
    roots = roots[-len(ops):]
    wall = sum(t1 - t0 for _k, _v, t0, t1, _b in ops)
    return (wall - sum(r["duration_s"] for r in roots)) / len(ops)
