"""Share of the engine loop's turns' time in which the host, not the device,
set the pace: Δ`turn_unwaited_s` / Δ`loop_turn_s`, the wall of the turns
whose fetch found its tokens ready (it returned in under
`llm/_engine.UNWAITED_S`: the device had finished the step before the loop
asked) over the wall of all turns that fetched a step. Near 0 the host's work
runs under the device's and costs a step nothing, whatever
`engine_host_ms_per_step` reads."""
from benchmark.layer_metrics import delta

UNIT, LAYER, SOURCE, MOVES = "%", "engine scheduler", "program_counter", "out_tokens_per_s"


def read(art):
    if "turn_unwaited_s" not in (art.get("stats_close") or {}):
        return None
    turn_s = delta(art, "loop_turn_s")
    if not turn_s:
        return None
    return 100.0 * delta(art, "turn_unwaited_s") / turn_s
