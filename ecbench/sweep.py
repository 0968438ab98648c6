"""Several runs of cells in one call, each a process of its own (this
one never touches JAX, so each child gets the chip), sharing the placed
compile cache. Result lines go to chiprun_out/<tag>.jsonl; the summary
gives each metric's median and its spread (interquartile distance over
the median, by statistics.quantiles(n=4)).

    python3 ecbench/sweep.py --tag enc10 --workload vol1g-10p4.encode \\
        --seeds 11,12,13 --seconds 10 [--trace 1] [--sets 2]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent


def spread(values: list[float]) -> float:
    if len(values) < 2:
        return float("nan")
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / abs(med) if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tag", required=True)
    ap.add_argument("--workload", required=True, help="cell, or cells with commas")
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--sets", type=int, default=1, help="repeat the seeds so many times")
    a = ap.parse_args(argv)
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = a.seconds if a.seconds is not None else manifest["run_seconds"]
    seeds = [int(s) for s in a.seeds.split(",")]
    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    out_path = out_dir / f"{a.tag}.jsonl"
    rc_all = 0
    for cell in a.workload.split(","):
        rows = []
        for set_no in range(a.sets):
            for seed in seeds:
                cmd = manifest["command"] + [
                    "--workload", cell, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(a.trace),
                ]
                t0 = time.perf_counter()
                p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
                wall = time.perf_counter() - t0
                last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
                try:
                    doc = json.loads(last)
                except ValueError:
                    doc = None
                row = {
                    "cell": cell, "set": set_no, "seed": seed, "seconds": seconds,
                    "trace": a.trace, "rc": p.returncode, "wall_s": wall, "result": doc,
                    "stderr_tail": p.stderr[-3000:],
                }
                with open(out_path, "a") as f:
                    f.write(json.dumps(row) + "\n")
                ok = doc is not None and doc.get("correct") is True and p.returncode == 0
                rc_all |= 0 if ok else 1
                vals = {k: v["value"] for k, v in (doc or {}).get("metrics", {}).items()}
                print(
                    f"{cell} set={set_no} seed={seed} rc={p.returncode} wall={wall:.1f}s "
                    f"correct={doc and doc.get('correct')} "
                    + " ".join(f"{k}={v:.6g}" for k, v in vals.items()),
                    flush=True,
                )
                if not ok:
                    print(p.stderr[-3000:], flush=True)
                rows.append((set_no, vals))
        for set_no in range(a.sets):
            mine = [v for s, v in rows if s == set_no and v]
            # a set's first run may compile: shown, and left out of setup_s
            for name in sorted({k for v in mine for k in v}):
                series = [v[name] for v in mine if name in v]
                if name == "setup_s" and set_no == 0:
                    series = series[1:]
                if series:
                    print(
                        f"  {cell} set={set_no} {name}: median={statistics.median(series):.6g} "
                        f"spread={spread(series) * 100:.2f}% n={len(series)} "
                        f"min={min(series):.6g} max={max(series):.6g}",
                        flush=True,
                    )
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
