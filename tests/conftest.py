"""Test harness: force an 8-device virtual CPU platform before jax imports.

The tests run on the CPU; sharding tests use a virtual 8-device CPU
mesh. The chip is reached only by `python chip_smoke.py` through the
chip tool.
"""

import os
import pathlib
import sys

# Force JAX_PLATFORMS=cpu; spawned-server subprocesses inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
from __graft_entry__ import _force_virtual_cpu_mesh  # noqa: E402

# Eight virtual CPU devices.
_force_virtual_cpu_mesh(8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection lifecycle tests "
        "(fixed-seed subset stays in tier-1; randomized soaks are slow)",
    )
    config.addinivalue_line(
        "markers", "slow: long soak tests excluded from tier-1 (-m 'not slow')"
    )


@pytest.fixture(autouse=True)
def _fault_registry_hygiene():
    """A test that armed fault points must never leak them into the next
    test — chaos determinism depends on a clean registry per test."""
    yield
    from seaweedfs_tpu import faults

    if faults.active():
        faults.clear()


@pytest.fixture
def disarmed():
    """The tracer is process-wide, and with `--dist loadfile` another
    file's tests ran on this worker before these: a recorder they left
    armed, or root spans left in the ring, must not decide a test that
    asserts on the disarmed state. Put the tracer back before, and
    leave it so."""
    from seaweedfs_tpu.utils import trace

    trace.configure(enabled=False, slow_op_s=0.0)
    trace.reset()
    yield trace
    trace.configure(enabled=False, slow_op_s=0.0)
    trace.reset()


@pytest.fixture(scope="session")
def rng():
    import numpy as np

    return np.random.default_rng(0x5EAD)


# ---------------------------------------------------------------- ports

_issued_ports: set[int] = set()


def allocate_port() -> int:
    """Ephemeral port that avoids previously issued ports AND their
    +10000 shadows (servers bind grpc on port+10000)."""
    import socket as _socket

    while True:
        with _socket.socket() as s:
            s.bind(("localhost", 0))
            p = s.getsockname()[1]
        if p + 10000 > 65535:
            continue  # grpc shadow would not be bindable
        if (
            p in _issued_ports
            or (p + 10000) in _issued_ports
            or (p - 10000) in _issued_ports
        ):
            continue
        # the shadow must actually be free right now too
        try:
            with _socket.socket() as s2:
                s2.bind(("localhost", p + 10000))
        except OSError:
            continue
        _issued_ports.add(p)
        _issued_ports.add(p + 10000)
        return p


def wait_for(cond, timeout=15.0, msg="condition"):
    """Poll until cond() is true or fail with msg — the one wait loop
    shared by worker/soak/cluster tests."""
    import time as _time

    deadline = _time.time() + timeout
    while not cond():
        if _time.time() > deadline:
            raise TimeoutError(msg)
        _time.sleep(0.05)
