"""`rs_roofline` for a slice that holds two kinds of Reed-Solomon work:
the least time the chip could take for the rebuilds in the slice (the k
rows they read and the lost rows they write) and for the
reconstructions in the slice (what their `ec.degraded_read` spans count
as carried to the device and back: k sibling rows up, one row down), as
a share of the time the device was busy in the slice, in per cent. A
span that lies half inside the slice counts half, as an operation does.
Busy time is the union of all device operations, so the share means the
same whatever kernels do the work."""

from ecbench import roofline
from ecbench.layerlib import bytes_in_slice, get_roots, walk
from ecbench.spanlib import NS


def reconstruction_bytes_in_slice(obs) -> tuple[float, float]:
    """(bytes up, bytes down) of the GETs' reconstructions in the slice."""
    lo, hi = obs.slice_t
    up = down = 0.0
    for root in get_roots(obs):
        for d in walk(root):
            if d["op"] != "ec.degraded_read" or d["end_ns"] <= d["start_ns"]:
                continue
            t0, t1 = d["start_ns"] / NS, d["end_ns"] / NS
            inside = (min(t1, hi) - max(t0, lo)) / (t1 - t0)
            if inside > 0:
                up += inside * d["attrs"].get("h2d_bytes", 0)
                down += inside * d["attrs"].get("d2h_bytes", 0)
    return up, down


def read(obs, cell):
    if obs.device is None or obs.slice_t is None or obs.device["busy_s"] <= 0:
        return None
    peaks = cell.peaks.get(cell.device_kind)
    if peaks is None:
        return None  # no peak, no share: never a guess
    k = int(cell.config["layout"]["data_shards"])
    lost = len(cell.traffic["rebuild_lost_shards"])
    rebuilt = bytes_in_slice(obs)
    up, down = reconstruction_bytes_in_slice(obs)
    if rebuilt <= 0 and up <= 0:
        return None
    # a reconstruction's bytes up are its k rows, its bytes down its one
    nbytes = roofline.rs_bytes(rebuilt, k, lost) + up + down
    ops = roofline.rs_ops(rebuilt, k, lost) + roofline.rs_ops(up, k, 1)
    least = max(
        nbytes / float(peaks["hbm_bytes_per_s"]), ops / float(peaks["int8_ops_per_s"])
    )
    return 100.0 * least / obs.device["busy_s"]
