"""The two probes shown to disagree where they should, on whatever host
this runs on: both alone for some seconds, then beside ONE thread that
holds the interpreter in a pure-Python loop. The Python probe's wait goes
to the interpreter's switch interval; the native twin's stays. Not a test
(tests/test_interp_probe.py holds the same on the CPU), and no run of the
benchmark calls it; it needs no chip, only the chip's host:

    python3 ecbench/tests/probe_spinner.py [seconds]
"""

import json
import os
import pathlib
import sys
import threading
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent.parent))

from seaweedfs_tpu.utils import interp_probe, trace  # noqa: E402


def cut(t0: int, t1: int) -> dict:
    docs = trace.traces(op=interp_probe.SPAN_OP)
    found = {
        key: interp_probe.summary(
            [w for d in docs for t, w in d["attrs"].get(samples, ()) if t0 <= t <= t1]
        )
        for key, samples in (("py_wait_ns", "py_samples"), ("core_wait_ns", "core_samples"))
    }
    # what the probes themselves cost: CPU by class over the spans that
    # lie inside the cut, beside those spans' wall time
    whole = [d for d in docs if t0 <= d["start_ns"] and d["end_ns"] <= t1]
    found["wall_ns"] = sum(d["end_ns"] - d["start_ns"] for d in whole)
    found["cpu_ns"] = {
        cls: sum(d["attrs"]["cpu_ns"].get(cls, 0) for d in whole)
        for cls in sorted({c for d in whole for c in d["attrs"]["cpu_ns"]})
    }
    return found


def spin(until_ns: int) -> None:
    while time.perf_counter_ns() < until_ns:
        pass


def main(seconds: float) -> dict:
    trace.configure(enabled=True, ring_size=4096)
    try:
        t0 = time.perf_counter_ns()
        time.sleep(seconds)
        t1 = time.perf_counter_ns()
        spinner = threading.Thread(target=spin, args=(t1 + int(seconds * 1e9),))
        spinner.start()
        spinner.join()
        t2 = time.perf_counter_ns()
        time.sleep(2 * interp_probe.INTERVAL_NS / 1e9)  # the last interval closes
        return {
            "switch_interval_s": sys.getswitchinterval(),
            "cpus": len(os.sched_getaffinity(0)),
            "alone": cut(t0, t1),
            "beside_a_python_spinner": cut(t1, t2),
        }
    finally:
        trace.configure(enabled=False)


if __name__ == "__main__":
    print(json.dumps(main(float(sys.argv[1]) if len(sys.argv) > 1 else 3.0)))
