"""python3 ecbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and
prints one JSON object as the last line of standard output. Exits
non-zero, with no result line, where JAX finds no TPU or fewer chips
than the cell asks for, or where the program is not in the checkout.
"""

import time

STARTED = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        import seaweedfs_tpu  # noqa: F401 - the system under test
    except ImportError as e:
        print(f"ecbench: the program is not in this checkout: {e}", file=sys.stderr)
        return 4
    from ecbench import harness
    from ecbench.cluster import BenchError

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    try:
        harness.run_cell(
            manifest, a.workload, a.seed, a.seconds, bool(a.trace), started=STARTED
        )
    except BenchError as e:
        print(f"ecbench: {e}", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    # servers leave daemon threads behind: leave without waiting on them
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    import os

    os._exit(rc)
