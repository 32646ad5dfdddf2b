"""Of the positions the window layers' decode rows would have read had the
layers been full, the share they did read: Δ`window_positions` (the step's
own count, summed over the window layers) / (Δ`attn_positions_live` x window
layers). 100 while every sequence is shorter than the window; 1,024 / L for
a context of L. What the rings save of a decode step's reads."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "%", "jitted steps", "program_counter", "out_tokens_per_s"


def read(art):
    if ("window_positions" not in (art.get("stats_close") or {})
            or "config" not in art):
        return None
    full = layer_metrics.delta(art, "attn_positions_live")
    if not full:
        return None
    cfg = art["config"]
    layers = sum(cfg["layer_types"][i] == "sliding_attention"
                 for i in cfg["layer_ids"])
    return 100.0 * layer_metrics.delta(art, "window_positions") / (full * layers)
