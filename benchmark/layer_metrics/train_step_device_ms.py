"""Device time of one train step: the mean duration of the executions of the
program `jit_train_step` on the trace's `XLA Modules` line, whole executions
inside the traced window, averaged over the chips (lib/xmeta.py). What
`train_step_p50_ms` would be were the host never in the way. Needs nothing
of the program but the jitted function's name: it reads on a program without
`TRAIN_SCOPES` too."""
from benchmark.lib import xmeta

UNIT, LAYER, SOURCE, MOVES = "ms", "train step", "device_trace", "train_tokens_per_s"


def read(art):
    r = xmeta.load_art(art)
    if not r or not r["step_device_s"]:
        return None
    return 1e3 * r["step_device_s"]
