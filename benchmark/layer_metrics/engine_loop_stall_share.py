"""Share of the engines' time spent in stalled turns of the loop:
Δ`loop_stall_s` (the wall of the turns that took over `llm/_engine
.STALL_TURN_S`, a second: a machine that stood still, an awaited compile)
over the interval between the two readings of the counters and the number of
engines. A window that lost a tenth of its tokens to a stall reads 10 here
and says so; one that ran slower steps reads 0."""
from benchmark.layer_metrics import delta

UNIT, LAYER, SOURCE, MOVES = "%", "engine scheduler", "program_counter", "out_tokens_per_s"


def read(art):
    if "loop_stall_s" not in (art.get("stats_close") or {}):
        return None
    stall_s = delta(art, "loop_stall_s")
    if stall_s is None:
        return None
    covers_s = art.get("count_covers_s") or art["window_s"]
    return 100.0 * stall_s / (covers_s * art["device"]["count"])
