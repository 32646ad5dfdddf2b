"""How late the load generator sent: send time minus due time, the worst of
the requests sent inside the window (a window holds 92 to about 200 sends, too
few for a p99 that is not the maximum in disguise). A starved generator must
not be read as the server: a closed loop whose value exceeds 5% of the median
request time, or an open loop whose value exceeds half the mean gap between
arrivals, is not `correct` (runners/serve_dp.py, `LIMITS`)."""

UNIT, LAYER, SOURCE, MOVES = "ms", "load generator", "host_clock", "out_tokens_per_s"


def read(art):
    late = art.get("gen_late_s")
    return max(late) * 1e3 if late else None
