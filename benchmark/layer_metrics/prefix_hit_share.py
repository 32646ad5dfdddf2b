"""Share of prompt tokens served from cached KV blocks instead of prefilled:
blocks reused over the window x block size / prompt tokens of the requests
sent in it. (`misses` counts requests and `block_hits` blocks, so their
ratio would mix units.)"""
UNIT, LAYER, SOURCE, MOVES = "%", "prefix cache", "program_counter", "out_tokens_per_s"


def read(art):
    a, b = art.get("stats_open"), art.get("stats_close")
    if not a or not b or not b.get("prefix_cache"):
        return None
    t0, t1 = art["t_open"], art["t_open"] + art["window_s"]
    prompt = sum(r["prompt_tokens"] for r in art["records"]
                 if t0 <= r["sent"] < t1)
    if not prompt:
        return None
    hits = b["prefix_cache"]["block_hits"] - a["prefix_cache"]["block_hits"]
    return 100.0 * hits * art["engine"]["kv_block_size"] / prompt
