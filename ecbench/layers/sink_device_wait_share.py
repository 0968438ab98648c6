"""Per cent of a volume operation's wall time during which the
pipeline's sink thread was inside `device_drain`, waiting for a batch to
come back from the device (the union of the stage's intervals over the
root span's duration)."""

from ecbench.spanlib import stage_share_of_wall


def read(obs, cell):
    return stage_share_of_wall(obs, "device_drain")
