"""The engine's step against the memory roofline: the bytes the window's
steps must move (lib/bytes_joyai.py: the weights outside the routed experts
once a step, each touched held expert once, the head, the decode rows' live
latents once a layer and what a chunk's rows see; from the engine's counters
over the window) / the chip's bandwidth (lib/peaks.py) / the steps' device
time (`decode_device_ms_per_step` x Δ`steps`). A latent counts once whatever
the program reads; a step with 256 chunk rows over a long context is
compute-bound, so this reads under what a decode-only step would."""
from benchmark.layer_metrics import decode_device_ms_per_step
from benchmark.lib import bytes_joyai, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "jitted steps", "device_trace", "out_tokens_per_s"


def read(art):
    step_ms = decode_device_ms_per_step.read(art)
    d = bytes_joyai.window_counters(art)
    if not step_ms or "config" not in art or d is None:
        return None
    need = bytes_joyai.step_bytes(art["config"], d)
    art["joyai_step_bytes"] = {k: v / d["steps"] for k, v in need.items()}
    bandwidth = peaks.peaks_for(art["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["total"] / bandwidth / (d["steps"] * step_ms * 1e-3)
