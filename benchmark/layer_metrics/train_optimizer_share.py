"""Share of the device's busy time spent in the optimizer: scope `optimizer`
of `models.llama.TRAIN_SCOPES` (`tx.update` and `apply_updates`), exclusive
time (lib/xmeta.py). None on a program that wrote no scope."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "train step", "device_trace", "train_tokens_per_s"


def read(art):
    return xmeta.share(art, "optimizer", ("fwd", "bwd"))
