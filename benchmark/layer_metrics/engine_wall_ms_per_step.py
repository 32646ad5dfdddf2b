"""Wall time per decode step of one engine: window / decode steps. Prefill
stalls, host work and idle waiting between steps are all inside (the name
says wall): it is what a token of a running request waits for."""
from benchmark.layer_metrics import delta

UNIT, LAYER, SOURCE, MOVES = "ms", "jitted steps", "program_counter", "out_tokens_per_s"


def read(art):
    steps = delta(art, "steps")
    if not steps:
        return None
    return 1e3 * art["window_s"] * art["device"]["count"] / steps
