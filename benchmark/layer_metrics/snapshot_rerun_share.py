"""Of the prompt tokens the prefix cache matched, the share that ran again
because the deepest state snapshot lay before the matched blocks' end:
Δ`snapshot_rerun_tokens` / (Δ`block_hits` x block size). The snapshots are a
widest chunk (256 tokens) apart, so a document of 12k tokens reads ~1%; a
pool too small for the sessions, or a policy that loses the deep snapshots,
reads towards 100 (every question runs its document again)."""
from benchmark import layer_metrics

UNIT, LAYER, SOURCE, MOVES = "%", "prefix cache", "program_counter", "out_tokens_per_s"


def read(art):
    a, b = art.get("stats_open"), art.get("stats_close")
    if (not a or not b or "snapshot_rerun_tokens" not in b
            or not b.get("prefix_cache")):
        return None
    hits = b["prefix_cache"]["block_hits"] - a["prefix_cache"]["block_hits"]
    if not hits:
        return None
    return (100.0 * layer_metrics.delta(art, "snapshot_rerun_tokens")
            / (hits * art["engine"]["kv_block_size"]))
