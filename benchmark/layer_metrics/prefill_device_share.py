"""Share of the device's busy time spent in prefill: the executions of
`jit_paged_prefill` and `jit_paged_suffix_prefill` on the `XLA Modules` line
over `busy_s` of the same window, averaged over the chips. The rest is the
decode step."""
from benchmark.lib import host_spans

UNIT, LAYER, SOURCE, MOVES = "%", "jitted steps", "device_trace", "out_tokens_per_s"


def read(art):
    r = host_spans.load(art)
    busy_s = (art.get("trace") or {}).get("busy_s")
    if not r or not r["modules"] or not busy_s:
        return None
    if not any(host_spans.module_events(
            r, (host_spans.DECODE_MODULE,) + host_spans.PREFILL_MODULES)):
        return None   # a program that does not name its steps
    lo, hi = r["window"]
    prefill = host_spans.module_events(r, host_spans.PREFILL_MODULES)
    inside_s = host_spans.NS * sum(
        min(s + d, hi) - max(s, lo) for events in prefill for _, s, d in events)
    return 100.0 * inside_s / len(prefill) / busy_s
