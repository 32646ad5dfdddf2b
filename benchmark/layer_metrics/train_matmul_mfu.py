"""What the matmuls reach of the chip's bf16 peak while they run: Σ XLA's
`model_flops` of the operations `train_matmul_share` counts, over Σ their
time, over the published peak (lib/peaks.py). XLA counts what it executes
(the loss's recomputed logits are in it), so this is the matmul units'
utilization, not the model's: `train_mfu` is that. Needs nothing of the
program: it reads on a program without `TRAIN_SCOPES` too."""
from benchmark.lib import peaks, xmeta

UNIT, LAYER, SOURCE, MOVES = "%", "kernels", "device_trace", "train_tokens_per_s"


def read(art):
    r = xmeta.load_art(art)
    if not r or not r["matmul_s"]:
        return None
    peak = peaks.peaks_for(art["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * r["matmul_flops"] / r["matmul_s"] / peak
