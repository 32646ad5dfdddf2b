"""Median time from sending a request to its whole answer, as a caller of
the closed loop sees it. Set by the number of callers as much as by the
system, so it explains the throughput and is not judged."""
from benchmark.lib import stats

UNIT, LAYER, SOURCE, MOVES = "s", "client view", "host_clock", "out_tokens_per_s"


def read(art):
    v = art.get("closed_req_s")
    return stats.median(v) if v else None
