"""Median wall time of a train step (host clock around
`block_until_ready(loss)`), over the steps that were not traced."""
from benchmark.lib import stats

UNIT, LAYER, SOURCE, MOVES = "ms", "train step", "host_clock", "train_tokens_per_s"


def untraced(art):
    skip = set(art.get("traced_steps") or [])
    return [s for i, s in enumerate(art.get("step_s") or []) if i not in skip]


def read(art):
    v = untraced(art)
    return stats.median(v) * 1e3 if v else None
