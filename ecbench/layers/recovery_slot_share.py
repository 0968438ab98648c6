"""Per cent of the device queue's window (its slots times the window's
wall time) that recovery batches held: the growth of the queue's
slot-seconds of class `recovery` across the window, which the driver
puts into `obs.counters`. Each batch is counted when its slot is
released, whole."""


def read(obs, cell):
    held = obs.counters.get("queue_slot_seconds")
    slots = obs.counters.get("queue_window")
    wall = obs.t_end - obs.t_start
    if held is None or not slots or wall <= 0:
        return None
    return 100.0 * held.get("recovery", 0.0) / (slots * wall)
