"""Share of the device's busy time spent in the forward pass of the layer
scan: scope `layers` of `models.llama.TRAIN_SCOPES` (the scan over `_layer`
and the final norm), under no `transpose(` and no `rematted_computation`,
exclusive time (lib/xmeta.py). None on a program that wrote no scope."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "train step", "device_trace", "train_tokens_per_s"


def read(art):
    return xmeta.share(art, "layers", ("fwd",))
