"""Roofline share of the flash-attention kernels: the least time the chip
could take for each call (the larger of operations / peak FLOP/s and bytes /
peak bandwidth, from lib/flops.py and lib/peaks.py), summed, over the time
the calls took in the trace. The calls are told apart by their signature
(lib/xplane.py), since the program gives its kernels no names; a Pallas call
that matches none is left out of both sums. `art["flash_bound"]` says which
roof bounds each kind."""
from benchmark.lib import flops, peaks, xplane

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "train_tokens_per_s"


def read(art):
    t = art.get("trace")
    if not t or not t.get("pallas_events"):
        return None
    pk = peaks.peaks_for(art["device"]["kind"])
    least = took = 0.0
    bound = {}
    for name, _start, dur in t["pallas_events"]:
        shape = xplane.flash_call_shape(name)
        if shape is None:
            continue
        kind = shape.pop("kind")
        roof = flops.roofline_seconds(
            flops.flash_kernel_cost(kind, causal=True, **shape), pk)
        least += roof["seconds"]
        took += dur * 1e-9
        bound[kind] = roof["bound"]
    art["flash_bound"] = bound
    return 100.0 * least / took if took else None
