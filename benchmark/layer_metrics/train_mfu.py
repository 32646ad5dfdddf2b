"""Model FLOP/s utilization: operations the forward and backward passes need
per token (lib/flops.py: matmul parameters without the embedding lookup, plus
causal attention; recomputed work not counted) x tokens per second over the
untraced steps / (chips x the chip's published bf16 peak)."""
from benchmark.layer_metrics.train_step_p50_ms import untraced
from benchmark.lib import flops, peaks

UNIT, LAYER, SOURCE, MOVES = "%", "train step", "host_clock", "train_tokens_per_s"


def read(art):
    steps = untraced(art)
    if not steps or art["device"]["platform"] != "tpu":
        return None
    per_token = flops.train_flops_per_token(art["hp"], art["seq_len"])
    rate = art["tokens_per_step"] * len(steps) / sum(steps)
    peak = peaks.peaks_for(art["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * per_token * rate / (art["chips"] * peak)
