"""Host time of one `train.report` call in the training loop (the worker's
side of the train controller), median over the window's reports."""
from benchmark.lib import stats

UNIT, LAYER, SOURCE, MOVES = "ms", "train controller", "host_clock", "train_tokens_per_s"


def read(art):
    v = art.get("report_s")
    return stats.median(v) * 1e3 if v else None
