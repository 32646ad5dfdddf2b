"""Of the requests that resumed from a state snapshot, the share whose
snapshot another request's prompt had left: Δ`snapshots_shared` /
Δ`snapshots_restored`. What a policy that takes a snapshot only where
prompts part is for: one prompt pays the rerun and leaves it, every later
one behind the same header resumes from it."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "%", "prefix cache", "program_counter", "out_tokens_per_s"


def read(art):
    if "snapshots_shared" not in (art.get("stats_close") or {}):
        return None
    restored = layer_metrics.delta(art, "snapshots_restored")
    if not restored:
        return None
    return 100.0 * layer_metrics.delta(art, "snapshots_shared") / restored
