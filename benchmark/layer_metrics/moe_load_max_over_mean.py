"""How uneven the held experts' load is: the most rows on one held expert in
a layer-step (Δ`moe_load_max`, summed over layer-steps) over the mean rows a
held expert gets (Δ`moe_pairs_held` / held experts). 1 would be a perfectly
even router; a grouped matmul's time follows the touched experts and its
longest group."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "ratio", "expert routing", "program_counter", "out_tokens_per_s"


def read(art):
    if "moe_load_max" not in (art.get("stats_close") or {}) or "config" not in art:
        return None
    held = layer_metrics.delta(art, "moe_pairs_held")
    if not held:
        return None
    return (layer_metrics.delta(art, "moe_load_max")
            * art["config"]["num_experts"] / held)
