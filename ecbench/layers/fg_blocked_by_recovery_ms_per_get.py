"""Milliseconds of `admission_wait` on the `ec.degraded_read` spans that
found the device queue's window full and laid it to recovery (their
`window_full` event, `by` = `recovery`), per GET of the window, healthy
ones included: what a rebuild's hold on the window costs the reads."""

from ecbench.harness import load_module
from ecbench.layerlib import get_roots

blocked_by_recovery = load_module("layers", "fg_blocked_by_recovery_share").blocked_by_recovery


def read(obs, cell):
    roots = get_roots(obs)
    if not roots or "queue_slot_seconds" not in obs.counters:
        return None
    waited = sum(
        d["stages"].get("admission_wait", {"seconds": 0.0})["seconds"]
        for r in roots for d in blocked_by_recovery(r)
    )
    return 1e3 * waited / len(roots)
