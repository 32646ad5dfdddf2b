"""Share of the engine loop's time in which the engine held no request: the
summed `engine:idle` annotations (`PagedEngine._run_loop`'s wait for the next
request, `llm/_engine.PHASE_IDLE`) over the loop's wall time, whole turns of
the loop inside the traced window; 0 when there was none (and on a program
without the annotation). It is what `open_engine_host_ms_per_step` holds
beside the host's work in a cell that runs two slots of 32. Only an empty
engine writes it, so only the open cell lists it."""
from benchmark.lib import host_spans

UNIT, LAYER, SOURCE, MOVES = "%", "engine scheduler", "program_span", "req_p50_s"
IDLE = "engine:idle"


def read(art):
    r = host_spans.load(art)
    if not r or not r["loop"]:
        return None
    idle_s, _ = host_spans.total_s(r["loop"], IDLE)
    return 100.0 * idle_s / r["loop_wall_s"]
