"""What a step that carries a prompt chunk takes, by the engine loop's own
account: Σ Δ`turn_s_w<w>` / Σ Δ`steps_w<w>` over the chunk widths w > 0, the
wall of the turns that fetched such a step over their number. Beside
`decode_turn_ms`, and weighted with it by `chunk_step_share`, it is
`engine_wall_ms_per_step` of an engine that was never empty. None where the
window held no chunk step (and in an engine whose prompts run whole)."""
import re

from benchmark.layer_metrics import delta

UNIT, LAYER, SOURCE, MOVES = "ms", "jitted steps", "program_counter", "out_tokens_per_s"
STEPS_AT_WIDTH = re.compile(r"steps_w([1-9]\d*)")


def read(art):
    widths = [m.group(1) for m in map(
        STEPS_AT_WIDTH.fullmatch, art.get("stats_close") or {}) if m]
    if not widths or not art.get("stats_open"):
        return None
    steps = sum(delta(art, f"steps_w{w}") for w in widths)
    if not steps:
        return None
    return 1e3 * sum(delta(art, f"turn_s_w{w}") for w in widths) / steps
