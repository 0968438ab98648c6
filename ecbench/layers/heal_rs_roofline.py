"""`mixed_rs_roofline` for a slice that holds heals from peers beside
the GETs' reconstructions: the least time the chip could take for the
heals in the slice (the k source rows each read, the lost rows it
writes: its source bytes x (k + lost) / k) and for the reconstructions
in the slice (the `h2d_bytes` + `d2h_bytes` of their `ec.degraded_read`
spans), over the HBM peak, as a share of the time the device was busy
in the slice, in per cent. A heal reads its source bytes from its
`ec.peer_rebuild` span; one that lies half inside the slice counts
half."""

from ecbench import roofline
from ecbench.harness import load_module
from ecbench.spanlib import NS

H = load_module("layers", "heal_mb_per_s")
mixed = load_module("layers", "mixed_rs_roofline")


def heal_bytes_in_slice(obs) -> float:
    lo, hi = obs.slice_t
    total = 0.0
    for d in H.heals(obs):
        t0, t1 = d["start_ns"] / NS, d["end_ns"] / NS
        inside = min(t1, hi) - max(t0, lo)
        if inside > 0 and t1 > t0:
            total += H.source_bytes(d) * inside / (t1 - t0)
    return total


def read(obs, cell):
    if obs.device is None or obs.slice_t is None or obs.device["busy_s"] <= 0:
        return None
    peaks = cell.peaks.get(cell.device_kind)
    if peaks is None:
        return None  # no peak, no share: never a guess
    k = int(cell.config["layout"]["data_shards"])
    lost = len(cell.traffic["heal_lost_shards"])
    healed = heal_bytes_in_slice(obs)
    up, down = mixed.reconstruction_bytes_in_slice(obs)
    if healed <= 0 and up <= 0:
        return None
    nbytes = roofline.rs_bytes(healed, k, lost) + up + down
    ops = roofline.rs_ops(healed, k, lost) + roofline.rs_ops(up, k, 1)
    least = max(
        nbytes / float(peaks["hbm_bytes_per_s"]), ops / float(peaks["int8_ops_per_s"])
    )
    return 100.0 * least / obs.device["busy_s"]
