"""The decode step against the memory roofline: the bytes a step must move
(lib/bytes_ling.py: the weights outside the routed experts once, each touched
held expert once, each active slot's state read and written once, the live
latents once; from the engine's counters over the window) / the chip's
bandwidth (lib/peaks.py) / `decode_device_ms_per_step`. Memory bounds a step
of 64 rows: its matmuls are under a tenth of the chip's FLOP/s."""
from benchmark import layer_metrics
from benchmark.layer_metrics import decode_device_ms_per_step
from benchmark.lib import bytes_ling, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "jitted steps", "device_trace", "out_tokens_per_s"


def read(art):
    step_ms = decode_device_ms_per_step.read(art)
    if (not step_ms or "config" not in art
            or "moe_experts_touched" not in (art.get("stats_close") or {})):
        return None
    steps = layer_metrics.delta(art, "steps")
    if not steps:
        return None
    cfg = art["config"]
    pairs_per_slot = (cfg["num_experts_per_tok"]
                      * bytes_ling.weight_bytes(cfg)["moe_layers"])
    need = bytes_ling.decode_step_bytes(
        cfg,
        layer_metrics.delta(art, "moe_pairs_routed") / pairs_per_slot / steps,
        layer_metrics.delta(art, "moe_experts_touched") / steps,
        layer_metrics.delta(art, "latent_positions_live") / steps)
    art["decode_step_bytes"] = need
    bandwidth = peaks.peaks_for(art["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["total"] / bandwidth / (step_ms * 1e-3)
