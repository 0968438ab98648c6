"""How sw_trace_events.json and span_docs.json beside this file were
recorded: one traced run of a cell, whose slice's first events (device
operations, the program's `sw:` annotations, the drivers' `ecbench.`
ones) are kept before the harness removes the trace, together with the
window's root span documents. Not a test, and no run of the benchmark
calls it.

    python3 ecbench/tests/record_sw_events.py <workload> <seed> <out_dir>

Needs the chip, as `ecbench/run.py` does.
"""

import json
import pathlib
import sys
import time

STARTED = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))

from ecbench import harness, spanlib, tracered  # noqa: E402

KEEP_S = 0.6  # of the slice, from its first device operation on


def program_compiles() -> float:
    """`sw_ec_compiles_total`, the program's own count, off /metrics."""
    from seaweedfs_tpu.utils import metrics

    for line in metrics.REGISTRY.render().decode().splitlines():
        if line.startswith("sw_ec_compiles_total "):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def main(workload: str, seed: str, out_dir: str) -> None:
    from seaweedfs_tpu.utils import trace

    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    reduce_dir = tracered.reduce_dir

    def reduce_and_record(trace_dir: str) -> dict:
        events = spanlib.load_events(tracered.newest_xplane(trace_dir))
        lo = min(s for _p, _n, s, _d in events["device"])
        hi = lo + KEEP_S
        kept = {
            "device": [e for e in events["device"] if lo <= e[2] < hi],
            # whatever was open in that window, begun before it or not
            "host": [e for e in events["host"] if e[2] < hi and e[2] + e[3] > lo],
        }
        (out / f"{workload}.sw_trace_events.json").write_text(json.dumps(kept))
        (out / f"{workload}.spans.json").write_text(json.dumps(trace.traces()))
        return reduce_dir(trace_dir)

    tracered.reduce_dir = reduce_and_record  # the harness's one reading of the slice
    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == workload)
    traffic = harness.load_json(harness.HERE / "traffic" / f"{cell['traffic']}.json")
    driver = harness.load_module("drivers", traffic["driver"])
    window = driver.window

    def window_and_compiles(cell, state, slice_):
        before = program_compiles()
        obs = window(cell, state, slice_)
        print(
            f"record: sw_ec_compiles_total {before:.0f} at the window's start, grew by "
            f"{program_compiles() - before:.0f} over it (compiles_in_window is the "
            "benchmark's own count of the same window)",
            file=sys.stderr, flush=True,
        )
        return obs

    driver.window = window_and_compiles
    harness.run_cell(manifest, workload, int(seed), 20.0, True, started=STARTED)


if __name__ == "__main__":
    main(*sys.argv[1:4])
    sys.stdout.flush()
    sys.stderr.flush()
    import os

    os._exit(0)  # the servers' threads never end: leave as run.py does
