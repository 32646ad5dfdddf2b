"""Engine-side time from `generate_stream` to the first emitted token:
`PagedEngine.stats()["ttft_p50_s"]` at the close of the window, the median of
the engine's last 256 requests (queue wait and prefill; not what a client
sees, the served path does not stream)."""
UNIT, LAYER, SOURCE, MOVES = "ms", "engine scheduler", "program_counter", "out_tokens_per_s"


def read(art):
    v = (art.get("stats_close") or {}).get("ttft_p50_s")
    return None if v is None else v * 1e3
