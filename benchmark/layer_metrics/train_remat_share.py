"""Share of the device's busy time spent recomputing: the operations whose
`tf_op` holds `rematted_computation` (JAX writes it under `jax.checkpoint`),
whatever their scope, exclusive time (lib/xmeta.py). Taken out first: the
scope shares beside it hold none of it. Needs nothing of the program but the
name stack JAX writes: it reads on a program without `TRAIN_SCOPES` too."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "train step", "device_trace", "train_tokens_per_s"


def read(art):
    return xmeta.share(art, None, ("remat",))
