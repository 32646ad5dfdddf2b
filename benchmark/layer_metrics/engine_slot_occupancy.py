"""Share of decode slots that produced a token: tokens emitted over the
window / (decode steps x slots). The first token of a request comes from its
prefill and is counted too, so a full batch of short answers can read
slightly above 100."""
from benchmark.layer_metrics import delta

UNIT, LAYER, SOURCE, MOVES = "%", "engine scheduler", "program_counter", "out_tokens_per_s"


def read(art):
    tokens, steps = delta(art, "tokens_out"), delta(art, "steps")
    if not steps:
        return None
    return 100.0 * tokens / (steps * art["engine"]["max_num_seqs"])
