"""Share of the engine loop's time during which running sequences get no
token because a prompt is being admitted: the summed `engine:admit`
annotations (prefix match, the prefill program awaited, first sample) over
the loop's wall time, whole turns of the loop inside the traced window."""
from benchmark.lib import host_spans

UNIT, LAYER, SOURCE, MOVES = "%", "engine scheduler", "program_span", "out_tokens_per_s"


def read(art):
    r = host_spans.load(art)
    if not r or not r["loop"]:
        return None
    admit_s, _ = host_spans.total_s(r["loop"], host_spans.ADMIT)
    return 100.0 * admit_s / r["loop_wall_s"]
