"""The decode kernel `paged_decode_attention` over a pool of latents against
the memory roofline: the bytes its calls in the traced window must read
(lib/bytes_joyai.py: each decoding slot's live latents, each row of the pool
once, 1,280 B, whatever the program reads: the kernel is handed the pool as
keys and as values, so a kernel that fetches a row twice reads under half) /
the chip's bandwidth (lib/peaks.py) / the device time of the Pallas calls
named `paged_decode_attention` in the trace. The positions are the engine's
count over the whole window scaled to the traced calls: a call is one layer
of one step."""
from benchmark.lib import bytes_joyai, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"
KERNEL = "%paged_decode_attention"


def read(art):
    t = art.get("trace")
    d = bytes_joyai.window_counters(art)
    if not t or "config" not in art or d is None:
        return None
    calls = [dur for name, _s, dur in t.get("pallas_events") or ()
             if name.startswith(KERNEL)]
    if not calls:
        return None
    cfg = art["config"]
    per_call = (bytes_joyai.decode_attention_bytes(cfg, d)
                / d["steps"] / cfg["num_hidden_layers"])
    bandwidth = peaks.peaks_for(art["device"]["kind"])["hbm_bytes_per_s"]
    art["decode_attention_ms_per_call"] = 1e-6 * sum(calls) / len(calls)
    return 100.0 * per_call * len(calls) / bandwidth / (sum(calls) * 1e-9)
