"""The least time the chip could take for the slice's Reed-Solomon work
(the bytes it has to read and write over the HBM peak: bytes, not
operations, bound RS 10+4 on a v5e, see roofline.py) as a share of the
time the device was busy in the slice, in per cent. An encode writes m
rows for the k it reads, a rebuild the rows that were lost. Busy time is
the union of all device operations, so the share means the same whatever
kernel does the work."""

from ecbench import roofline
from ecbench.layerlib import bytes_in_slice


def read(obs, cell):
    nbytes = bytes_in_slice(obs)
    if obs.device is None or nbytes <= 0 or obs.device["busy_s"] <= 0:
        return None
    peaks = cell.peaks.get(cell.device_kind)
    if peaks is None:
        return None  # no peak, no share: never a guess
    layout = cell.config["layout"]
    if cell.traffic["op"] == "ec.rebuild":
        out_rows = len(cell.traffic["lost_shards"])
    else:
        out_rows = int(layout["parity_shards"])
    least, _bound_by = roofline.least_seconds(
        nbytes, int(layout["data_shards"]), out_rows, peaks
    )
    return 100.0 * least / obs.device["busy_s"]
