"""Share of the token-expert pairs the router chose, over the window's decode
steps and expert layers, that fell on experts this chip holds:
Δ`moe_pairs_held` / Δ`moe_pairs_routed`. The chip holds a quarter of the
experts (2 of 8 groups): about 25% says the router has neither collapsed onto
the share nor away from it."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "%", "expert routing", "program_counter", "out_tokens_per_s"


def read(art):
    if "moe_pairs_routed" not in (art.get("stats_close") or {}):
        return None
    routed = layer_metrics.delta(art, "moe_pairs_routed")
    if not routed:
        return None
    return 100.0 * layer_metrics.delta(art, "moe_pairs_held") / routed
