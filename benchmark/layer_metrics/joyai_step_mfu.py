"""The engine's step against the chip's peak: the model's operations of the
rows the window's steps really ran (lib/bytes_joyai.step_flops: chunk rows
and decode rows without padding, a held pair's expert, the head where logits
are read, the attention at the expanded form's count over the positions each
row sees) / the steps' device time (`decode_device_ms_per_step` x Δ`steps`) /
the chip's bf16 peak. The whole step's share of the peak, not a kernel's."""
from benchmark.layer_metrics import decode_device_ms_per_step
from benchmark.lib import bytes_joyai, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "jitted steps", "device_trace", "out_tokens_per_s"


def read(art):
    step_ms = decode_device_ms_per_step.read(art)
    d = bytes_joyai.window_counters(art)
    if not step_ms or "config" not in art or d is None:
        return None
    did = bytes_joyai.step_flops(art["config"], d)
    art["joyai_step_flops"] = {k: v / d["steps"] for k, v in did.items()}
    peak = peaks.peaks_for(art["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * did["total"] / peak / (d["steps"] * step_ms * 1e-3)
