"""Device discovery and compile-cache placement, in one place.

A TPU chip belongs to one process at a time, so the process that will
use the chip is the one that opens it: `local_devices()` asks JAX
in-process, once, and every "is there a TPU?" decision in the package
reads its cached answer. On a TPU the persistent compile cache is
placed here too, before the first compile, so the servers, the worker,
the smoke and the benchmark's cells all share one cache directory by
going through this module.
"""

from __future__ import annotations

import os
import pathlib
import threading
from typing import NamedTuple

from .glog import logger

# Fixed path: a cache that moves (tempfile, pid, timestamp) never hits.
DEFAULT_COMPILE_CACHE_DIR = str(
    pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
)


class DeviceInfo(NamedTuple):
    platform: str
    kind: str
    count: int


_lock = threading.Lock()
_info: DeviceInfo | None = None


def place_compile_cache() -> None:
    """Point JAX's persistent compile cache at a fixed directory.

    With `JAX_COMPILATION_CACHE_DIR` set JAX reads the variable itself
    and no directory is set here. The RS kernels compile in 0.2-2 s,
    under JAX's default 1 s floor for caching, so the floor goes to 0."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _why_cpu() -> str:
    """JAX's own reason for handing out the CPU."""
    import jax

    pinned = jax.config.jax_platforms
    if pinned:
        return f"jax_platforms={pinned}"
    from jax._src import xla_bridge

    err = getattr(xla_bridge, "_backend_errors", {}).get("tpu")
    return f"tpu backend: {err}" if err else "no tpu backend"


def local_devices() -> DeviceInfo:
    """(platform, kind, count) of this process's JAX devices.

    The first call opens the device in THIS process and logs what it
    found at warning level — a chip held by another process shows up
    as `cpu` with JAX's reason, never as a silent CPU."""
    global _info
    with _lock:
        if _info is None:
            import jax

            devs = jax.devices()
            info = DeviceInfo(
                devs[0].platform, str(devs[0].device_kind), len(devs)
            )
            if info.platform == "tpu":
                # Opening the device compiles nothing, so this is still
                # ahead of the first compile. CPU processes (the tests)
                # keep JAX's default: XLA:CPU reloads cached code with a
                # machine-feature complaint per entry.
                place_compile_cache()
            log = logger("devices")
            if info.platform == "cpu":
                log.warning("devices: cpu x%d (%s)", info.count, _why_cpu())
            else:
                log.warning(
                    "devices: %s %s x%d", info.platform, info.kind, info.count
                )
            _info = info
        return _info


def tpu_attached() -> bool:
    return local_devices().platform == "tpu"
