"""Seconds a volume operation's batches waited for admission to the
device queue, per operation."""

from ecbench.layerlib import done_ops, stage_seconds, volume_op_roots


def read(obs, cell):
    ops, roots = done_ops(obs), volume_op_roots(obs)
    if not ops or not roots:
        return None
    return stage_seconds(roots[-len(ops):], ("admission_wait",)) / len(ops)
