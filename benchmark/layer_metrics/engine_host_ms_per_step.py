"""What the host does per decode step while the device waits for it: the
engine loop's wall time less the time it waits for the device
(`engine:device_wait`) and less admissions (`engine:admit`, the stall share's
part), over the number of decode steps. Sweep, upload, dispatch, emit, the
two thread hops a step and whatever else runs on the engine's event loop
between its turns are all inside."""
from benchmark.lib import host_spans

UNIT, LAYER, SOURCE, MOVES = "ms", "engine scheduler", "program_span", "out_tokens_per_s"


def read(art):
    r = host_spans.load(art)
    if not r or not r["loop"]:
        return None
    wait_s, steps = host_spans.total_s(r["loop"], host_spans.DEVICE_WAIT)
    admit_s, _ = host_spans.total_s(r["loop"], host_spans.ADMIT)
    if not steps:
        return None
    return 1e3 * (r["loop_wall_s"] - wait_s - admit_s) / steps
