"""One run of one cell: find the cell's files by its name in
BENCHMARK.json, set up, measure for `--seconds`, decide `correct`
against the plain reference, print the result line.

Whatever belongs to one configuration, one traffic mix, one driver or
one per-layer metric is a file of its own, found by name:

    configs/<config>.json    the deployment, as it is run
    traffic/<traffic>.json   {"driver": <name>, ...its parameters}
    drivers/<driver>.py      setup / window / verify / teardown
    layers/<metric>.py       read(obs, cell) -> number, or None

so a later PR adds files and entries to BENCHMARK.json and edits none.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import threading
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
DATA_DIR = HERE / "_data"
TRACE_DIR = HERE / "_trace"

# a slice of the window is profiled: the trace of a whole window is
# hundreds of MB and its reading would outlast the run's time limit
SLICE_AFTER_S = 2.0
SLICE_SECONDS = 4.0


@dataclasses.dataclass
class Compared:
    """One number that `correct` rests on, beside its limit (None: a
    count shown for the reader, held to nothing)."""

    name: str
    value: float
    limit: float | None

    @property
    def ok(self) -> bool:
        return self.limit is None or self.value <= self.limit


@dataclasses.dataclass
class Observed:
    """What a driver's window hands back."""

    ops: list = dataclasses.field(default_factory=list)  # (kind, vid, t0, t1, bytes)
    gets: list = dataclasses.field(default_factory=list)  # (t0, t1)
    t_start: float = 0.0
    t_end: float = 0.0
    attempted: int = 0
    failed: int = 0
    bytes: int = 0
    end_to_end: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)
    spans: list = dataclasses.field(default_factory=list)  # root span docs
    device: dict | None = None  # tracered.reduce() of the slice
    slice_t: tuple[float, float] | None = None  # host clock of the slice


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    seed: int
    seconds: float
    traced: bool
    data_dir: str
    end_to_end: list[dict]
    per_layer: list[dict]
    peaks: dict | None = None
    device_kind: str = ""
    started: float = 0.0
    marks: list = dataclasses.field(default_factory=list)

    def mark(self, what: str) -> None:
        """Where set-up's time went: seconds since the process started."""
        self.marks.append((what, time.perf_counter() - self.started))


def annotate(name: str):
    """A host span in the profiler's own trace (nothing when no trace
    is being taken), so idle gaps can be laid to what the host did."""
    import jax

    return jax.profiler.TraceAnnotation(name)


# ------------------------------------------------------------- manifest


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    modname = f"ecbench_{kind}_{name}".replace("-", "_").replace(".", "_")
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def metric_cells(metric: dict, manifest: dict) -> set[str]:
    """The cells a metric is reported in: its `workloads`, or all."""
    return set(metric.get("workloads") or [w["name"] for w in manifest["workloads"]])


def resolve_cell(
    manifest: dict, workload: str, seed: int, seconds: float, traced: bool,
    overrides: dict | None = None,
) -> Cell:
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / cfg_entry["file"])
    config.update(overrides or {})
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=workload, config=config, traffic=traffic, chips=int(w["chips"]),
        seed=seed, seconds=seconds, traced=traced,
        data_dir=str(DATA_DIR / workload),
        end_to_end=[
            m for m in manifest["end_to_end"] if workload in metric_cells(m, manifest)
        ],
        per_layer=[
            m for m in manifest["per_layer"] if workload in metric_cells(m, manifest)
        ],
    )


# ---------------------------------------------------------------- slice


class TraceSlice:
    """Profiles a few seconds of the window. Drivers call `boundary()`
    between operations; the slice starts at the first boundary after
    `after_s` and stops at the first after `length_s` more, so that in a
    one-stream cell it holds whole operations."""

    def __init__(self, enabled: bool, trace_dir: str,
                 after_s: float = SLICE_AFTER_S, length_s: float = SLICE_SECONDS):
        self.enabled = enabled
        self.trace_dir = trace_dir
        self.after_s, self.length_s = after_s, length_s
        self._lock = threading.Lock()
        self._born = time.perf_counter()
        self.t0: float | None = None
        self.t1: float | None = None

    def boundary(self) -> None:
        if not self.enabled or self.t1 is not None:
            return
        import jax

        with self._lock:
            now = time.perf_counter()
            if self.t0 is None:
                if now - self._born >= self.after_s:
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0  # every Python call otherwise
                    jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
                    self.t0 = time.perf_counter()
            elif self.t1 is None and now - self.t0 >= self.length_s:
                self.t1 = now
                jax.profiler.stop_trace()

    def close(self) -> None:
        """End of the window: stop a slice that is still open."""
        if not self.enabled:
            return
        import jax

        with self._lock:
            if self.t0 is not None and self.t1 is None:
                self.t1 = time.perf_counter()
                jax.profiler.stop_trace()


# ------------------------------------------------------------------ run


def find_devices(cell: Cell, require_tpu: bool):
    """JAX's devices, or BenchError where they are not the cell's chips."""
    from ecbench.cluster import BenchError
    from seaweedfs_tpu.utils import devices

    info = devices.local_devices()
    if require_tpu and info.platform != "tpu":
        raise BenchError(f"no accelerator: JAX reports {info}")
    if require_tpu and info.count < cell.chips:
        raise BenchError(f"{cell.name} needs {cell.chips} chips, JAX reports {info}")
    return info


def read_layers(cell: Cell, obs: Observed) -> dict:
    """The `metrics` of a traced run: what the reader file of each
    per-layer metric that lists the cell finds in the window; a reader
    that finds nothing leaves its metric out."""
    from ecbench.cluster import BenchError

    metrics = {}
    for m in cell.per_layer:
        value = load_module("layers", m["name"]).read(obs, cell)
        if value is None:
            continue
        if m["name"].endswith("_roofline") and not 0 < value <= 100:
            # bytes counted too high, or time that leaves out work
            raise BenchError(f"{m['name']} reads {value} %: malformed")
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(
    manifest: dict, workload: str, seed: int, seconds: float, traced: bool,
    require_tpu: bool = True, overrides: dict | None = None,
    control: bool = False, started: float | None = None, out=sys.stdout,
) -> dict:
    """The whole run; returns the result line as a dict (and prints it).
    `require_tpu=False` and `overrides` are the CPU rehearsal's: the
    command never passes them. `control` puts the broken reference in
    the program's place at the comparison."""
    started = time.perf_counter() if started is None else started
    cell = resolve_cell(manifest, workload, seed, seconds, traced, overrides)
    cell.started = started
    info = find_devices(cell, require_tpu)
    cell.mark("devices")
    from ecbench import cluster as C
    from ecbench import tracered

    cell.peaks = load_json(HERE / "peaks.json")
    cell.device_kind = info.kind
    if require_tpu and info.kind not in cell.peaks:
        raise C.BenchError(f"no peaks for device kind {info.kind!r} in peaks.json")
    driver = load_module("drivers", cell.traffic["driver"])
    meter = C.CompileMeter()
    # stale directories of a run that died must not slow this one
    shutil.rmtree(DATA_DIR, ignore_errors=True)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    os.makedirs(cell.data_dir)
    mnt, fstype = C.filesystem_of(cell.data_dir)
    print(
        f"ecbench: {cell.name} seed={seed} seconds={seconds} trace={int(traced)} "
        f"device={info.platform}:{info.kind}x{info.count} "
        f"data_dir={cell.data_dir} on {mnt} ({fstype}"
        f"{', a memory filesystem: fsync costs nothing' if fstype in C.MEMORY_FILESYSTEMS else ''})",
        file=sys.stderr, flush=True,
    )
    state = None
    try:
        state = driver.setup(cell)
        setup_s = time.perf_counter() - started
        cell.mark("ready")
        print(
            "ecbench: set-up, seconds since the process started: "
            + " ".join(f"{what}={t:.2f}" for what, t in cell.marks),
            file=sys.stderr, flush=True,
        )
        c0, _ = meter.snapshot()
        slice_ = TraceSlice(traced, str(TRACE_DIR))
        if traced:
            from seaweedfs_tpu.utils import trace

            trace.reset()  # the warm-up's spans are not the window's
        obs = driver.window(cell, state, slice_)
        c1, _ = meter.snapshot()
        obs.counters["compiles_in_window"] = c1 - c0
        obs.end_to_end["setup_s"] = setup_s
        peak = C.memory_peak_bytes()
        device = {
            "platform": info.platform, "kind": info.kind, "count": info.count,
            "memory_peak_bytes": peak,
        }
        breakdown = None
        if traced:
            obs.spans = trace.traces()
            if obs.ops:
                from ecbench import layerlib

                print(
                    "ecbench: root span and stage seconds of each operation: "
                    + layerlib.describe_volume_ops(obs),
                    file=sys.stderr, flush=True,
                )
            if slice_.t0 is not None:
                obs.slice_t = (slice_.t0, slice_.t1)
                obs.device = tracered.reduce_dir(str(TRACE_DIR))
                print(
                    "ecbench: device planes of the trace "
                    f"{[p for p in obs.device['planes'] if p[0].startswith('/device')]}",
                    file=sys.stderr,
                )
                device["busy_s"] = obs.device["busy_s"]
                device["window_s"] = slice_.t1 - slice_.t0
                breakdown = {
                    "device_ops": obs.device["device_ops"][:10],
                    "idle_gaps": obs.device["idle_gaps"][:10],
                }
        if traced and require_tpu and not device.get("busy_s", 0) > 0:
            raise C.BenchError("the traced slice holds no device operation")
        compared = driver.verify(cell, state, obs, control=control)
        metrics = {}
        if traced:
            metrics = read_layers(cell, obs)
        else:
            for m in cell.end_to_end:
                metrics[m["name"]] = {
                    "value": obs.end_to_end[m["name"]], "unit": m["unit"],
                }
        correct = all(c.ok for c in compared)
        result = {
            "correct": correct, "attempted": obs.attempted, "failed": obs.failed,
            "metrics": metrics, "device": device,
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        result["compared"] = {
            c.name: {"value": c.value, "limit": c.limit} for c in compared
        }
    finally:
        if state is not None:
            with contextlib.suppress(Exception):
                driver.teardown(state)
        shutil.rmtree(DATA_DIR, ignore_errors=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    for c in compared:
        print(
            f"ecbench: compared {c.name} = {c.value} "
            f"(limit {'none' if c.limit is None else c.limit})"
            f"{'' if c.ok else '  <-- over its limit'}",
            file=sys.stderr, flush=True,
        )
    print(json.dumps(result), file=out, flush=True)
    return result
