"""Share of the device's busy time spent in matmuls: the operations XLA's
own `hlo_category` calls `convolution fusion` or `convolution`, whatever
their scope or pass (lib/xmeta.py). Needs nothing of the program: it reads
on a program without `TRAIN_SCOPES` too."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "train_tokens_per_s"


def read(art):
    r = xmeta.load_art(art)
    busy = (art.get("trace") or {}).get("busy_s")
    if not r or not busy:
        return None
    return 100.0 * r["matmul_s"] / busy
