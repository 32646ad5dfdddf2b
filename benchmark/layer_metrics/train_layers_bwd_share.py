"""Share of the device's busy time spent in the backward pass of the layer
scan: scope `layers` of `models.llama.TRAIN_SCOPES` under a `transpose(`,
the recomputation inside it (`train_remat_share`) left out, exclusive time
(lib/xmeta.py). None on a program that wrote no scope."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "train step", "device_trace", "train_tokens_per_s"


def read(art):
    return xmeta.share(art, "layers", ("bwd",))
