"""Share of the device's busy time spent in the loss: scope `loss` of
`models.llama.TRAIN_SCOPES` (the head's matmuls, softmax, the chunk loops and
the head's collectives), forward and backward, exclusive time
(lib/xmeta.py). None on a program that wrote no scope."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "train step", "device_trace", "train_tokens_per_s"


def read(art):
    return xmeta.share(art, "loss", ("fwd", "bwd"))
