"""Device time of one decode step: the mean duration of the executions of
the program `jit_paged_decode_step` on the trace's `XLA Modules` line, whole
executions inside the traced window, averaged over the chips. No host time,
no prefill: what `engine_wall_ms_per_step` would be were the device never
kept waiting."""
from benchmark.lib import host_spans

UNIT, LAYER, SOURCE, MOVES = "ms", "jitted steps", "device_trace", "out_tokens_per_s"


def read(art):
    r = host_spans.load(art)
    if not r:
        return None
    lo, hi = r["window"]
    per_chip = []
    for events in host_spans.module_events(r, (host_spans.DECODE_MODULE,)):
        # an execution the trace's end may have cut would pull the mean
        # down: the last one is left out
        whole = [d for _, s, d in events if s >= lo and s + d < hi]
        if whole:
            per_chip.append(sum(whole) / len(whole))
    if not per_chip:
        return None
    return 1e3 * host_spans.NS * sum(per_chip) / len(per_chip)
