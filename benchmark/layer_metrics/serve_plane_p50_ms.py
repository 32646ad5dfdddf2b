"""Time a request spends in the serve plane (proxy, handle, replica,
DPEngineGroup) and not in the engine: its ingress span minus the
`completions_stream` execution span under it, median over the requests whose
spans were recorded. Traced run only (`tracing_enabled`).

The two are paired by walking up `parent_span_id` from the engine's span to
the ingress span: a trace id is not a request (the proxy's spans of many
requests hang under one trace)."""
from benchmark.lib import stats

UNIT, LAYER, SOURCE, MOVES = "ms", "serve plane", "program_span", "out_tokens_per_s"


def read(art):
    spans = {s["span_id"]: s for s in art.get("spans") or []
             if s["duration_s"] is not None}
    both = []
    for s in spans.values():
        if "completions_stream" not in s["name"]:
            continue
        up = s
        for _ in range(8):
            up = spans.get(up["parent_span_id"])
            if up is None or up["name"].startswith("ingress:"):
                break
        if up is not None and up["name"].startswith("ingress:"):
            both.append(up["duration_s"] - s["duration_s"])
    art["serve_plane_pairs"] = len(both)
    return stats.median(both) * 1e3 if both else None
