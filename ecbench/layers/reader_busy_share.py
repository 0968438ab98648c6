"""Per cent of a volume operation's wall time during which the
pipeline's reader thread was inside `disk_read` (the union of the
stage's intervals over the root span's duration): near 100, the reads
set the pace; well under, the reader waits for the stages after it."""

from ecbench.spanlib import stage_share_of_wall


def read(obs, cell):
    return stage_share_of_wall(obs, "disk_read")
