"""Of the latents the decode rows' attention read over the window, the share
that lay in blocks more than one admitted request held (a tenant's system
prompt, read by every session of the tenant): Δ`attn_positions_shared` /
Δ`attn_positions_live`, each a layer's count (the layers cancel). What one
reading of a shared prefix a step, and not one a row, would save."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "%", "prefix cache", "program_counter", "out_tokens_per_s"


def read(art):
    close = art.get("stats_close") or {}
    if ("attn_positions_shared" not in close
            or "latent_positions_read" not in close):
        return None
    live = layer_metrics.delta(art, "attn_positions_live")
    if not live:
        return None
    return 100.0 * layer_metrics.delta(art, "attn_positions_shared") / live
