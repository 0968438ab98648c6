"""The benchmark's own tests run on the CPU: no chip, no topology
described at import. `pytest ecbench/tests` from the root of the repo."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parent.parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import faulthandler  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def no_test_hangs():
    """A test that hangs (a server that never stops) is ended with the
    stacks of all threads, not left to eat the caller's time limit."""
    faulthandler.dump_traceback_later(300, exit=True)
    yield
    faulthandler.cancel_dump_traceback_later()


def pytest_sessionfinish(session, exitstatus):
    session.config._ecbench_exitstatus = int(exitstatus)


def pytest_unconfigure(config):
    """The in-process servers leave threads behind that never end, and
    the interpreter would wait for them: leave as run.py does, once the
    summary is out."""
    status = getattr(config, "_ecbench_exitstatus", None)
    if status is not None:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(status)
