"""How trace_events.json beside this file was recorded: the first
events of a real trace, enough to check the reducer's arithmetic against
by hand. Not a test, and no run of the benchmark calls it.

    python3 ecbench/tests/record_trace_events.py <trace_dir> <out.json>

`<trace_dir>` is what `jax.profiler.start_trace` wrote to (the harness
removes its own at the end of a run: keep a copy to record from).
"""

import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent.parent))

from ecbench import tracered  # noqa: E402

if __name__ == "__main__":
    trace_dir, out = sys.argv[1:3]
    events = tracered.load_events(tracered.newest_xplane(trace_dir))
    with open(out, "w") as f:
        json.dump(
            {"device": events["device"][:300], "host": events["host"][:40],
             "planes": events["planes"]}, f,
        )
