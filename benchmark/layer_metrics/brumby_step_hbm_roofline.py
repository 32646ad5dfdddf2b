"""The engine's step against the memory roofline: the bytes the window's
steps must move (lib/bytes_brumby.py: the weights and the head once a step,
the decoding slots' and the chunk's slot's state read and written, a
snapshot's bytes where one is taken or restored; from the engine's counters
over the window) / the chip's bandwidth (lib/peaks.py) / the steps' device
time (`decode_device_ms_per_step` x Δ`steps`). A step with 256 chunk rows is
partly compute-bound, so this reads under what a decode-only step would."""
from benchmark.layer_metrics import decode_device_ms_per_step
from benchmark.lib import bytes_brumby, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "jitted steps", "device_trace", "out_tokens_per_s"


def read(art):
    step_ms = decode_device_ms_per_step.read(art)
    d = bytes_brumby.window_counters(art)
    if not step_ms or "config" not in art or d is None:
        return None
    need = bytes_brumby.step_bytes(art["config"], d)
    art["brumby_step_bytes"] = {k: v / d["steps"] for k, v in need.items()}
    bandwidth = peaks.peaks_for(art["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * need["total"] / bandwidth / (d["steps"] * step_ms * 1e-3)
