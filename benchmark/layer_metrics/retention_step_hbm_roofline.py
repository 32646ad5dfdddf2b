"""The decode kernel `retention_step` against the memory roofline: the bytes
its calls in the traced window must move (lib/bytes_brumby.py: each decoding
slot's state and normaliser, every layer's, read once and written once,
counted at the symmetric square's 8,256 rows whatever the program holds) /
the chip's bandwidth (lib/peaks.py) / the device time of the Pallas calls
named `retention_step` in the trace. The rows are the engine's count over
the whole window scaled to the traced calls: a call is one layer of one
step, and a step decodes Δ`rows_decoded` / Δ`steps` slots on average."""
from benchmark.lib import bytes_brumby, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "out_tokens_per_s"
KERNEL = "%retention_step"


def read(art):
    t = art.get("trace")
    d = bytes_brumby.window_counters(art)
    if not t or "config" not in art or d is None:
        return None
    calls = [dur for name, _s, dur in t.get("pallas_events") or ()
             if name.startswith(KERNEL)]
    if not calls:
        return None
    cfg = art["config"]
    # one call is one layer's share of a step's rows
    per_call = (bytes_brumby.retention_step_bytes(cfg, d["rows_decoded"])
                / d["steps"] / cfg["num_hidden_layers"])
    bandwidth = peaks.peaks_for(art["device"]["kind"])["hbm_bytes_per_s"]
    art["retention_step_ms_per_call"] = 1e-6 * sum(calls) / len(calls)
    return 100.0 * per_call * len(calls) / bandwidth / (sum(calls) * 1e-9)
