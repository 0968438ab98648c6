"""Per cent of the process's CPU in the window that threads the program
did not start read (`other_python` of the probe spans' `cpu_ns`, over
`process_cpu_ns`): in these cells the load generator, whose clients are
threads of the server's process and pass its interpreter lock."""

from ecbench import probelib


def read(obs, cell):
    cpu = probelib.cpu_seconds(obs)
    if cpu is None or cpu[1] <= 0:
        return None
    by_class, process, _wall = cpu
    return 100.0 * by_class.get("other_python", 0.0) / process
