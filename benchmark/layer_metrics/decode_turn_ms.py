"""What a step without a prompt chunk takes, by the engine loop's own account
(`PagedEngine._account_turn`, PR 62): Δ`turn_s_w0` / Δ`steps_w0`, the wall of
the loop's turns that fetched such a step over their number, summed over the
whole window with a profiler or none. Where the device sets the pace a turn
is the time the program takes for that kind of step, which
`decode_device_ms_per_step` averages over both kinds in one traced second;
where the host does (`engine_unwaited_turn_share`), the host's turn."""
from benchmark.layer_metrics import delta

UNIT, LAYER, SOURCE, MOVES = "ms", "jitted steps", "program_counter", "out_tokens_per_s"


def read(art):
    if "steps_w0" not in (art.get("stats_close") or {}):
        return None
    steps = delta(art, "steps_w0")
    if not steps:
        return None
    return 1e3 * delta(art, "turn_s_w0") / steps
