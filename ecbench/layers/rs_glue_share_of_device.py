"""Per cent of the slice's device seconds (the sum over all device
operations) spent in operations other than the Reed-Solomon kernel,
which is found by its fixed name: the shape glue around it."""

from ecbench.spanlib import KERNEL_NAME


def read(obs, cell):
    if obs.device is None:
        return None
    ops = obs.device["device_ops"]
    kernel = sum(s for name, s in ops if name.split(".")[0] == KERNEL_NAME)
    total = sum(s for _name, s in ops)
    if kernel <= 0 or total <= 0:
        return None  # no kernel of that name: nothing to take a share of
    return 100.0 * (total - kernel) / total
