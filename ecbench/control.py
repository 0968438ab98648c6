"""The control of a cell, on the chip at the cell's own size: the same
run as run.py makes, but at the comparison the reference with one
guarantee broken stands in the program's place, and `correct` has to
come out false (drivers' `verify(..., control=True)` say what is broken).
The benchmark's own runs never run this.

    python3 ecbench/control.py --workload vol1g-10p4.encode --seeds 1,2,3 --seconds 3

Exit code 0 when every seed read not correct. One process per seed; this
one does not touch JAX.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def one(workload: str, seed: int, seconds: float) -> int:
    from ecbench import harness

    manifest = harness.load_json(ROOT / "BENCHMARK.json")
    result = harness.run_cell(manifest, workload, seed, seconds, False, control=True)
    return 0 if result["correct"] is False else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell, or cells with commas")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=3.0)
    a = ap.parse_args(argv)
    seeds = [int(s) for s in a.seeds.split(",")]
    cells = a.workload.split(",")
    if len(seeds) == 1 and len(cells) == 1:
        rc = one(cells[0], seeds[0], a.seconds)
        sys.stdout.flush()
        sys.stderr.flush()
        import os

        os._exit(rc)
    failed = 0
    for cell in cells:
        for seed in seeds:
            p = subprocess.run(
                [sys.executable, __file__, "--workload", cell, "--seeds", str(seed),
                 "--seconds", str(a.seconds)],
                cwd=ROOT, capture_output=True, text=True,
            )
            last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else "{}"
            try:
                compared = json.loads(last).get("compared")
            except ValueError:
                compared = None
            print(f"control {cell} seed={seed} rc={p.returncode} compared={json.dumps(compared)}",
                  flush=True)
            if p.returncode != 0:
                print(p.stderr[-2000:], flush=True)
                failed += 1
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
