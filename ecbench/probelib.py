"""What the readers of the program's wait probes share.

Armed, the program's tracer runs two probes (seaweedfs_tpu/utils/
interp_probe.py): a Python thread and a native one that sleep 5 ms in a
loop and record how late they woke; the first waits for a core AND the
interpreter, the second for a core alone. Every 100 ms the program closes
a root span `interp.probe` whose attributes hold the interval's samples
(`py_samples`, `core_samples`: `[wake_ns, wait_ns]` on the spans' clock)
and the CPU that each class of thread read in it (`cpu_ns` by class,
`process_cpu_ns`). Beside them, every native call of a worker that comes
back to the interpreter books its wait for it on the span it served
(`interp_wait_ns`, `interp_returns`).

A program without the probes closes no such span and books no such
attribute: every function here then returns None, and the reader leaves
its metric out.

    python3 ecbench/probelib.py <spans.json>

prints the tables of `describe` for span documents that
`ecbench/tests/record_sw_events.py` kept (the rebuild cell lists no
metric of these: its readings are quoted from there).
"""

from __future__ import annotations

import bisect
import json
import pathlib
import sys

if __name__ == "__main__":  # run as a script: the checkout is not on the path yet
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from ecbench import tracered
from ecbench.layerlib import get_roots, walk
from ecbench.spanlib import NS, stage_intervals, window_op_roots

PROBE_OP = "interp.probe"
SAMPLES = {"py": "py_samples", "core": "core_samples"}
# classes whose threads run Python: what they read is, but for the
# native calls they wait in, time with the interpreter held
NOT_PYTHON = ("native",)


def probe_spans(obs) -> list[dict]:
    return [d for d in obs.spans if d["op"] == PROBE_OP]


def samples(obs, which: str) -> list[tuple[float, float]] | None:
    """(wake time in s, wait in ms) of every sample of the `py` or the
    `core` probe in the window; None where no span carries any."""
    key = SAMPLES[which]
    spans = [d for d in probe_spans(obs) if key in d["attrs"]]
    if not spans:
        return None
    return [(t / NS, w / 1e6) for d in spans for t, w in d["attrs"][key]]


def inside(samples_, intervals) -> list[tuple[float, float]]:
    """The samples that woke inside one of the (disjoint, sorted)
    intervals."""
    starts = [lo for lo, _hi in intervals]
    out = []
    for t, w in samples_:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < intervals[i][1]:
            out.append((t, w))
    return out


def quantile(waits: list[float], q: float) -> float | None:
    """Nearest rank, as the program's own summary takes it."""
    if not waits:
        return None
    s = sorted(waits)
    return s[min(int(q * len(s)), len(s) - 1)]


def median_wait_ms(obs, which: str, intervals=None) -> float | None:
    got = samples(obs, which)
    if got is None:
        return None
    if intervals is not None:
        got = inside(got, intervals)
    return quantile([w for _t, w in got], 0.5)


def pipeline_intervals(obs) -> list[tuple[float, float]] | None:
    """When a rebuild's pipeline ran: the union of the `disk_read`
    intervals of the window's volume operations, in seconds on the
    spans' clock; None where no operation left any."""
    ivs = [
        iv for root in window_op_roots(obs) for iv in stage_intervals(root, "disk_read")
    ]
    return tracered.union(ivs) if ivs else None


def seam_per_get(obs, name: str) -> float | None:
    """The seam attribute `name` summed under the window's GET roots,
    per GET; None where no span under them carries it."""
    roots = get_roots(obs)
    found = [d["attrs"][name] for r in roots for d in walk(r) if name in d["attrs"]]
    if not roots or not found:
        return None
    return sum(found) / len(roots)


def cpu_seconds(obs) -> tuple[dict[str, float], float, float] | None:
    """(CPU seconds by class of thread, the process's CPU seconds, wall
    seconds) over the window's probe spans; None without one."""
    spans = [d for d in probe_spans(obs) if "cpu_ns" in d["attrs"]]
    if not spans:
        return None
    by_class: dict[str, float] = {}
    for d in spans:
        for cls, ns in d["attrs"]["cpu_ns"].items():
            by_class[cls] = by_class.get(cls, 0.0) + ns / NS
    process = sum(d["attrs"]["process_cpu_ns"] for d in spans) / NS
    wall = sum(d["end_ns"] - d["start_ns"] for d in spans) / NS
    return by_class, process, wall


# ------------------------------------------------------------ the tables


def _row(name: str, waits: list[float]) -> str:
    if not waits:
        return f"{name}: no sample"
    return (
        f"{name}{': ' if name else ''}n={len(waits)} p50={quantile(waits, 0.5):.3f} "
        f"p95={quantile(waits, 0.95):.3f} mean={sum(waits) / len(waits):.3f} "
        f"max={max(waits):.3f} ms"
    )


def describe(obs, out=sys.stderr) -> None:
    """Everything the probes and the seam stamps say about one window,
    for PERF.md: CPU by class of thread, both probes' waits over the
    window and inside and outside a running pipeline, and what a return
    to the interpreter costs by the span that paid it."""
    cpu = cpu_seconds(obs)
    if cpu is None:
        return
    by_class, process, wall = cpu
    python = sum(s for c, s in by_class.items() if c not in NOT_PYTHON)
    print(
        f"ecbench: probes: process CPU {process:.3f} s over {wall:.3f} s of probe "
        f"spans = {process / wall:.3f} cores busy; CPU seconds by class of thread "
        "(share of the process, share of the Python threads): "
        + " ".join(
            f"{c}={s:.3f}({100 * s / process:.1f}%,"
            f"{'-' if c in NOT_PYTHON else f'{100 * s / python:.1f}%'})"
            for c, s in sorted(by_class.items(), key=lambda kv: -kv[1])
        ),
        file=out, flush=True,
    )
    reader = pipeline_intervals(obs)
    cuts = []
    if reader:
        rebuilds = tracered.union([
            (d["start_ns"] / NS, d["end_ns"] / NS)
            for root in window_op_roots(obs) for d in walk(root) if d["op"] == "ec.rebuild"
        ])
        lo, hi = reader[0][0], reader[-1][1]
        cuts = [
            ("while a rebuild's reader ran", reader),
            ("while an ec.rebuild span was open", rebuilds),
            ("between the rebuilds' spans",
             tracered.subtract([(lo, hi)], rebuilds or reader)),
        ]
    for which, label in (("py", "interpreter (Python probe)"), ("core", "core (native probe)")):
        got = samples(obs, which)
        if got is None:
            continue
        print(f"ecbench: probes: wait for {label}, " + _row("window", [w for _t, w in got]),
              file=out, flush=True)
        for when, ivs in cuts:
            print(
                f"ecbench: probes:   {label} {when} ({tracered.length(ivs):.2f} s), "
                + _row("", [w for _t, w in inside(got, ivs)]),
                file=out, flush=True,
            )
    for kind, roots in (("GET", get_roots(obs)), ("volume operation", window_op_roots(obs))):
        by_op: dict[str, list[float]] = {}
        for r in roots:
            for d in walk(r):
                if "interp_returns" in d["attrs"]:
                    acc = by_op.setdefault(d["op"], [0.0, 0.0])
                    acc[0] += d["attrs"]["interp_returns"]
                    acc[1] += d["attrs"]["interp_wait_ns"] / 1e6
        if by_op:
            print(
                "ecbench: probes: returns to the interpreter at the native seams under "
                f"{len(roots)} {kind} roots, by the span that paid: "
                + " ".join(
                    f"{op}: {n:.0f} returns, {ms / n:.3f} ms each, {ms / len(roots):.3f} ms a root;"
                    for op, (n, ms) in sorted(by_op.items())
                ),
                file=out, flush=True,
            )


if __name__ == "__main__":
    from ecbench import harness

    for path in sys.argv[1:]:
        seen = harness.Observed()
        seen.spans = json.loads(pathlib.Path(path).read_text())
        # every volume operation in the documents counts as the window's
        seen.ops = [
            ("op", 0, 0.0, 0.0, 0) for d in seen.spans
            if d["op"] in ("rpc.ec_shards_generate", "rpc.ec_shards_rebuild")
        ]
        print(path, file=sys.stderr)
        describe(seen)
