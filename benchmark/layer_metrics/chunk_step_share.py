"""Share of the dispatched steps that carried a prompt chunk:
Δ`steps_with_chunk` / Δ`steps`. One chunk rides a step, so where this reads
near 100 the chunk is the scarce thing and prompts queue behind each other;
where it reads low the steps are decode steps."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "%", "engine scheduler", "program_counter", "out_tokens_per_s"


def read(art):
    if "steps_with_chunk" not in (art.get("stats_close") or {}):
        return None
    steps = layer_metrics.delta(art, "steps")
    if not steps:
        return None
    return 100.0 * layer_metrics.delta(art, "steps_with_chunk") / steps
