"""Time a request waits inside the engine before its admission starts
(`generate_stream` -> a slot and blocks in hand): the median duration of the
`engine:queue` spans the engine records per request on the tracing plane
(`tracing_enabled`; `engine_ttft_p50_ms` is this plus the prefill). Every
request of the run counts, the few of warm-up and ramp among them."""
from benchmark.lib import stats

UNIT, LAYER, SOURCE, MOVES = "ms", "engine scheduler", "program_span", "out_tokens_per_s"


def read(art):
    waits = [s["duration_s"] for s in art.get("spans") or []
             if s["name"] == "engine:queue" and s["duration_s"] is not None]
    art["engine_queue_spans"] = len(waits)
    return stats.median(waits) * 1e3 if waits else None
